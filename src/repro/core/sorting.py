"""Steps 1-2 and 2: tile intersection and per-tile depth-ordered fragment lists.

GPU 3DGS builds dynamic per-tile fragment lists with atomic counters and a
global radix sort. Neither exists on TPU/XLA, so we build **static-capacity**
fragment lists: every tile owns ``K`` slots of Gaussian indices in ascending
depth order (``-1`` padding). Construction is a single global depth argsort +
a per-slot binary search over cumulative tile positions — no per-tile
sorting, no atomics.

Capacity overflow (more than K Gaussians on a tile) drops the *deepest*
fragments, which is the correct priority (near-opaque front fragments occlude
them anyway); the overflow count is reported so tests/benchmarks can assert
it stays negligible.

Fragment lists are *reused across the K masked iterations* of §4.1 adaptive
pruning (the paper reuses tile-intersection + sort results between pruning
intervals) — the SLAM pipeline caches the ``FragmentLists`` and only rebuilds
on interval boundaries or keyframes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.projection import ProjectedGaussians
from repro.obs.profiling import scoped

TILE = 16  # pixels per tile side (paper convention)


class TileGrid(NamedTuple):
    height: int  # image H (padded to tile multiple)
    width: int   # image W
    grid_h: int
    grid_w: int

    @property
    def num_tiles(self) -> int:
        return self.grid_h * self.grid_w


def make_tile_grid(height: int, width: int) -> TileGrid:
    assert height % TILE == 0 and width % TILE == 0, (
        f"image {height}x{width} must be a multiple of {TILE}; pad upstream"
    )
    return TileGrid(height, width, height // TILE, width // TILE)


class FragmentLists(NamedTuple):
    idx: jnp.ndarray       # (num_tiles, K) int32 Gaussian indices, -1 padded
    count: jnp.ndarray     # (num_tiles,) int32 fragments per tile (<= K)
    overflow: jnp.ndarray  # () int32 total dropped fragments
    total: jnp.ndarray     # () int32 total tile-Gaussian intersections (pre-drop)


@scoped("frag_build")
def build_fragment_lists(
    proj: ProjectedGaussians, grid: TileGrid, capacity: int,
    keep: jnp.ndarray | None = None,
) -> FragmentLists:
    """Vectorized tile-intersection + depth sort. Non-differentiable (indices).

    ``keep`` (an optional (N,) bool mask) drops Gaussians from the lists
    entirely — the sparse stable/unstable build passes ``~stable`` so frozen
    Gaussians emit no fragments and stable-only tiles end up with zero
    counts (which the WSU schedule then turns into zero-trip programs).
    An all-True ``keep`` produces lists identical to ``keep=None``."""
    mu2d = jax.lax.stop_gradient(proj.mu2d)
    depth = jax.lax.stop_gradient(proj.depth)
    radius = jax.lax.stop_gradient(proj.radius)
    valid = proj.valid
    if keep is not None:
        valid = valid & keep

    n = mu2d.shape[0]
    order = jnp.argsort(jnp.where(valid, depth, jnp.inf))  # near -> far
    mu_s = mu2d[order]
    rad_s = radius[order]
    val_s = valid[order]

    # Tile-space bounding boxes (inclusive).
    tx0 = jnp.clip(jnp.floor((mu_s[:, 0] - rad_s) / TILE), 0, grid.grid_w - 1).astype(jnp.int32)
    tx1 = jnp.clip(jnp.floor((mu_s[:, 0] + rad_s) / TILE), 0, grid.grid_w - 1).astype(jnp.int32)
    ty0 = jnp.clip(jnp.floor((mu_s[:, 1] - rad_s) / TILE), 0, grid.grid_h - 1).astype(jnp.int32)
    ty1 = jnp.clip(jnp.floor((mu_s[:, 1] + rad_s) / TILE), 0, grid.grid_h - 1).astype(jnp.int32)

    tiles_y = jnp.arange(grid.grid_h, dtype=jnp.int32)
    tiles_x = jnp.arange(grid.grid_w, dtype=jnp.int32)
    # Membership M[t, k_sorted]: Gaussian k covers tile t. (T, N) bool.
    in_y = (tiles_y[:, None] >= ty0[None, :]) & (tiles_y[:, None] <= ty1[None, :])  # (gh, N)
    in_x = (tiles_x[:, None] >= tx0[None, :]) & (tiles_x[:, None] <= tx1[None, :])  # (gw, N)
    m = (in_y[:, None, :] & in_x[None, :, :] & val_s[None, None, :]).reshape(
        grid.num_tiles, n
    )

    pos = jnp.cumsum(m.astype(jnp.int32), axis=1)  # 1-based position within tile
    total = jnp.sum(m.astype(jnp.int32))
    count = jnp.minimum(pos[:, -1], capacity)
    overflow = jnp.sum(jnp.maximum(pos[:, -1] - capacity, 0))

    # Slot j of tile t holds the tile's (j+1)-th member in depth order: the
    # first sorted position where its running count reaches j+1.  One binary
    # search per slot (T*K*log N reads) rather than a (T, N) scatter, which
    # the TPU compiler expands into code and compile time linear in N.
    slot = jnp.arange(1, capacity + 1, dtype=jnp.int32)
    first = jax.vmap(lambda p: jnp.searchsorted(p, slot, side="left"))(pos)
    out = jnp.where(slot[None, :] <= count[:, None],
                    order[jnp.minimum(first, n - 1)], -1).astype(jnp.int32)
    return FragmentLists(idx=out, count=count, overflow=overflow, total=total)


@scoped("frag_build")
def count_skipped_fragments(
    proj: ProjectedGaussians, grid: TileGrid, keep: jnp.ndarray
) -> jnp.ndarray:
    """() int32 — tile-Gaussian intersections a ``keep``-masked
    :func:`build_fragment_lists` omits relative to the dense build.

    A valid Gaussian's membership-row sum is exactly its clipped tile-bbox
    area, so the skipped total is the bbox-area sum over valid-but-dropped
    Gaussians — an (N,) computation, no (T, N) membership matrix.  The
    formulas mirror the build's clips so the count is exact (pre-capacity,
    like ``FragmentLists.total``)."""
    mu2d = jax.lax.stop_gradient(proj.mu2d)
    radius = jax.lax.stop_gradient(proj.radius)
    tx0 = jnp.clip(jnp.floor((mu2d[:, 0] - radius) / TILE), 0, grid.grid_w - 1)
    tx1 = jnp.clip(jnp.floor((mu2d[:, 0] + radius) / TILE), 0, grid.grid_w - 1)
    ty0 = jnp.clip(jnp.floor((mu2d[:, 1] - radius) / TILE), 0, grid.grid_h - 1)
    ty1 = jnp.clip(jnp.floor((mu2d[:, 1] + radius) / TILE), 0, grid.grid_h - 1)
    area = ((tx1 - tx0 + 1) * (ty1 - ty0 + 1)).astype(jnp.int32)
    dropped = proj.valid & ~keep
    return jnp.sum(jnp.where(dropped, area, 0))


def remap_fragment_rows(frags: FragmentLists, view_idx: jnp.ndarray) -> FragmentLists:
    """Translate fragment lists built over a paged *view* (rows 0..M-1) into
    storage-row indices: ``view_idx`` is the (M,) storage row behind each
    view row.  ``-1`` padding is preserved; counts/overflow/total are
    index-free and pass through.  When the view is the identity gather
    (every page visible, ascending), this is a no-op bitwise."""
    idx = frags.idx
    safe = jnp.maximum(idx, 0)
    return frags._replace(idx=jnp.where(idx >= 0, view_idx[safe], -1)
                          .astype(jnp.int32))


def stack_fragment_lists(lists: list["FragmentLists"]) -> FragmentLists:
    """Stack per-keyframe fragment lists along a new leading axis so the
    mapping scan can carry the whole window cache as one pytree
    (idx (W,T,K), count (W,T), overflow (W,), total (W,))."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *lists)


@scoped("frag_build")
def update_fragment_slot(stack: FragmentLists, i, fresh: FragmentLists) -> FragmentLists:
    """Write a freshly built list into window slot ``i`` of a stacked cache
    (the Obs. 6 stride-rebuild inside the mapping scan)."""
    return jax.tree.map(
        lambda s, f: jax.lax.dynamic_update_index_in_dim(s, f, i, axis=0),
        stack,
        fresh,
    )


def balanced_pair_permutation(count: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Heavy-light fold of tiles into balanced work pairs (WSU pixel-level
    pairwise scheduling, adapted to tile granularity).

    Tiles are argsorted by fragment count and the heaviest is paired with the
    lightest, second-heaviest with second-lightest, etc., so every pair's
    total load approaches the mean.  For an odd tile count a zero-load
    duplicate of the lightest tile pads the schedule to an even number of
    slots; the duplicate always lands in slot 1 and does no work (see
    :mod:`repro.core.schedule`).

    Returns ``(perm, load)``, both ``(S,)`` with ``S = 2 * ceil(T / 2)``:
    ``perm[2p]``/``perm[2p+1]`` are pair ``p``'s heavy/light tile ids and
    ``load`` the fragment count each slot actually owes (0 for the pad slot).
    Pure jnp — safe to rebuild inside ``lax.scan`` bodies.
    """
    t = count.shape[0]
    p = (t + 1) // 2
    order = jnp.argsort(count).astype(jnp.int32)  # ascending; stable
    load = count[order].astype(jnp.int32)
    if 2 * p != t:  # odd: prepend a zero-load duplicate of the lightest tile
        order = jnp.concatenate([order[:1], order])
        load = jnp.concatenate([jnp.zeros((1,), jnp.int32), load])
    light, light_load = order[:p], load[:p]
    heavy, heavy_load = order[p:][::-1], load[p:][::-1]
    perm = jnp.stack([heavy, light], axis=1).reshape(-1)
    slot_load = jnp.stack([heavy_load, light_load], axis=1).reshape(-1)
    return perm, slot_load


def tile_churn_ratio(prev_count: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """§4.1 tile-Gaussian intersection change ratio controlling the pruning
    interval K (ratio > 5% -> K/2 else 2K)."""
    denom = jnp.maximum(jnp.sum(prev_count), 1)
    return jnp.sum(jnp.abs(count - prev_count)) / denom


def gather_tile_attributes(
    proj: ProjectedGaussians, frags: FragmentLists
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather per-tile fragment attributes into the packed layout consumed by
    the rasterizer: (num_tiles, 12, K) float32, attribute-major so each
    attribute row is lane-contiguous in VMEM.

    Rows: 0 mu_x, 1 mu_y, 2 conic_a, 3 conic_b, 4 conic_c,
          5 r, 6 g, 7 b, 8 opacity, 9 depth, 10 valid, 11 pad.
    """
    idx = frags.idx  # (T, K)
    safe = jnp.maximum(idx, 0)
    present = idx >= 0

    def take(x):  # (N,) -> (T,K)
        return jnp.where(present, x[safe], 0.0)

    attrs = jnp.stack(
        [
            take(proj.mu2d[:, 0]),
            take(proj.mu2d[:, 1]),
            take(proj.conic[:, 0]),
            take(proj.conic[:, 1]),
            take(proj.conic[:, 2]),
            take(proj.color[:, 0]),
            take(proj.color[:, 1]),
            take(proj.color[:, 2]),
            take(proj.opacity),
            take(proj.depth),
            present.astype(jnp.float32),
            jnp.zeros_like(idx, jnp.float32),
        ],
        axis=1,
    )  # (T, 12, K)
    return attrs, present
