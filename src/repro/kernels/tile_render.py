"""Pallas TPU forward rasterizer (Step 3: Alpha Computing + Alpha Blending).

Maps RTGS's Rendering Engine onto the TPU execution model:

* grid = one program per 16x16 tile; Pallas double-buffers the per-tile
  fragment block HBM->VMEM (the ASIC's "subtile streaming" becomes software
  pipelining over the grid).  ``tile_render_fwd_sched`` is the
  **WSU-scheduled** variant: one program per *balanced tile pair*, the pair
  permutation consumed via scalar prefetch (``PrefetchScalarGridSpec`` index
  maps pick each slot's attribute block straight from HBM — no host-side
  gather), and the chunk loop runs ``lax.fori_loop(0, trips)`` with the
  slot's actual trip count instead of the full capacity loop
  (see repro/core/schedule.py).
* alpha computing is vectorized over a fragment *chunk* x 256 pixels
  (the heavy exp stage, the paper's 12-cycle alpha-computing unit);
  the blend chain is an unrolled multiply-add loop over the chunk
  (the 3-cycle blending unit).
* chunk-level early termination: the chunk loop is a ``fori_loop`` bounded
  by the tile's *actual* trip count (``ceil(count / chunk)`` — subtile
  streaming), and a chunk whose pixels are all below TERM_EPS is skipped
  under ``lax.cond`` (TPU has no per-lane divergence, so the paper's
  per-pixel termination is hoisted to chunk granularity; semantics stay
  exact because ``include`` is a prefix property, see ref.py).
* the **R&B Buffer**: raw fragment alphas are stashed to ``stash`` so the
  backward kernel never re-evaluates the exp (paper: 20 -> 4 cycles). The
  backward replays the blend with multiplies only — no Eq.(5) division.

Layouts.  Pixel vectors are (1, 256) lane rows.  The attributes arrive
attribute-major, (12, K) per tile, and each program transposes its block
once into a fragment-major (K, 16) VMEM scratch (:func:`_load_columns`):
a chunk is then an aligned sublane slice ``cols[start:start + C]`` whose
columns broadcast across the pixel lanes, and a fragment's scalar is a
static (1, 1) slice of it.  Per-tile counts and schedules ride scalar
prefetch (SMEM).  The per-pixel outputs leave as one (5, 256) block per
tile — rows r, g, b, depth, final T — so every block's last two dims are
whole array dims or multiples of (8, 128), as the TPU compiler requires.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sorting import TILE, TileGrid
from repro.kernels import resolve_interpret
from repro.kernels.ref import ALPHA_MAX, ALPHA_MIN, NUM_ATTRS, PIX, TERM_EPS

DEFAULT_CHUNK = 16
COLS = 16        # fragment-major scratch width: NUM_ATTRS padded to sublanes
OUT_ROWS = 5     # per-pixel output rows: r, g, b, depth, final T


def _pixel_coords(tile_id, grid_w):
    """Pixel-center coords of this tile's 256 pixels, two (1, 256) f32."""
    ty = tile_id // grid_w
    tx = tile_id % grid_w
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, PIX), 1)
    px = (tx * TILE + lane % TILE).astype(jnp.float32) + 0.5
    py = (ty * TILE + lane // TILE).astype(jnp.float32) + 0.5
    return px, py


def _load_columns(attrs_ref, cols_ref):
    """Transpose this tile's (12, K) attribute block into the fragment-major
    (K, COLS) scratch: column ``a`` of row ``k`` is attribute ``a`` of
    fragment ``k``."""
    a = attrs_ref[0]
    pad = jnp.zeros((COLS - NUM_ATTRS, a.shape[1]), a.dtype)
    cols_ref[...] = jnp.concatenate([a, pad], axis=0).T


def _chunk_columns(cols_ref, start, chunk):
    """The chunk's (C, COLS) fragment rows (aligned dynamic sublane slice)."""
    return cols_ref[pl.ds(pl.multiple_of(start, chunk), chunk), :]


def _chunk_alphas(blk, px, py):
    """Vectorized Step 3-1 for one chunk: raw alphas (chunk, 256)."""
    mu_x, mu_y = blk[:, 0:1], blk[:, 1:2]     # (C,1)
    ca, cb, cc = blk[:, 2:3], blk[:, 3:4], blk[:, 4:5]
    o, present = blk[:, 8:9], blk[:, 10:11]

    dx = px - mu_x                            # (C,256)
    dy = py - mu_y
    q = ca * dx * dx + 2.0 * cb * dx * dy + cc * dy * dy
    gauss = jnp.exp(-0.5 * jnp.maximum(q, 0.0))
    alpha = jnp.minimum(o * gauss, ALPHA_MAX)
    alpha = jnp.where((alpha >= ALPHA_MIN) & (present > 0.5), alpha, 0.0)
    return alpha


def _blend_chunk(blk, alpha, chunk, carry):
    """The Step 3-2 blend chain over one chunk — shared op-for-op by the
    raster-order and WSU-scheduled kernels so both produce bit-identical
    accumulators."""
    acc_r, acc_g, acc_b, acc_d, trans = carry
    for i in range(chunk):
        a = alpha[i:i + 1, :]                       # (1,256)
        f = blk[i:i + 1, :]                         # fragment i's attributes
        include = (trans > TERM_EPS).astype(jnp.float32)
        am = a * include
        w = trans * am
        acc_r += w * f[:, 5:6]
        acc_g += w * f[:, 6:7]
        acc_b += w * f[:, 7:8]
        acc_d += w * f[:, 9:10]
        trans = trans * (1.0 - am)
    return acc_r, acc_g, acc_b, acc_d, trans


def _fwd_tile_loop(attrs_ref, cols_ref, stash_ref, out_ref, row, tile_id,
                   trips, grid_w, chunk):
    """The per-tile work shared by both forward kernels: stream ``trips``
    chunks (subtile streaming — the loop is bounded by actual load, not
    capacity), with chunk-level early termination once every pixel's
    transmittance is saturated, then write the tile's (5, 256) output rows.
    Identical loop structure in both kernels keeps their compiled float
    contraction — and therefore their outputs — bit-identical."""
    _load_columns(attrs_ref, cols_ref)
    px, py = _pixel_coords(tile_id, grid_w)
    stash_ref[row] = jnp.zeros(stash_ref.shape[1:], jnp.float32)
    carry0 = (
        jnp.zeros((1, PIX), jnp.float32), jnp.zeros((1, PIX), jnp.float32),
        jnp.zeros((1, PIX), jnp.float32), jnp.zeros((1, PIX), jnp.float32),
        jnp.ones((1, PIX), jnp.float32),
    )

    def trip_body(c, carry):
        start = c * chunk
        trans = carry[4]

        def do_chunk(carry=carry):
            blk = _chunk_columns(cols_ref, start, chunk)
            alpha = _chunk_alphas(blk, px, py)                # (C,256)
            stash_ref[row, pl.ds(pl.multiple_of(start, chunk), chunk), :] = alpha
            return _blend_chunk(blk, alpha, chunk, carry)

        return jax.lax.cond(jnp.max(trans) > TERM_EPS, do_chunk,
                            lambda carry=carry: carry)

    out = jax.lax.fori_loop(0, trips, trip_body, carry0)
    out_ref[row] = jnp.concatenate(out, axis=0)   # (5,256): r, g, b, d, T


def _split_outputs(out):
    """(T, 5, 256) kernel rows -> color (T,3,256), depth (T,256), final_T."""
    return out[:, 0:3], out[:, 3], out[:, 4]


def _fwd_kernel(count_ref, attrs_ref, out_ref, stash_ref, cols_ref,
                *, grid_w: int, chunk: int, tiles: int):
    # Stacked multi-view grids run B*T programs; the pixel coords of program
    # p belong to tile p mod T of its view (identity when unbatched).
    t = pl.program_id(0)
    trips = (count_ref[t] + chunk - 1) // chunk  # stream only the real load
    _fwd_tile_loop(attrs_ref, cols_ref, stash_ref, out_ref, 0, t % tiles,
                   trips, grid_w, chunk)


@functools.partial(
    jax.jit, static_argnames=("grid", "chunk", "interpret", "tiles_per_view"))
def tile_render_fwd(
    attrs: jnp.ndarray,   # (T, 12, K) — or (B*T, 12, K) stacked views
    count: jnp.ndarray,   # (T,) int32
    grid: TileGrid,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
    tiles_per_view: int | None = None,
):
    """Returns (color (T,3,256), depth (T,256), final_T (T,256), stash (T,K,256)).

    ``tiles_per_view`` enables **stacked-grid multi-view batching**: pass
    attrs/count for ``B`` views concatenated along the tile axis and the
    per-view tile count ``T``; the grid runs ``B*T`` programs whose per-tile
    computation is bit-identical to ``B`` separate calls.  ``interpret=None``
    derives the mode from the platform (:func:`resolve_interpret`)."""
    num_tiles, num_attrs, capacity = attrs.shape
    assert num_attrs == NUM_ATTRS and capacity % chunk == 0
    tiles = tiles_per_view or num_tiles
    assert num_tiles % tiles == 0, (num_tiles, tiles)

    kernel = functools.partial(_fwd_kernel, grid_w=grid.grid_w, chunk=chunk,
                               tiles=tiles)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[pl.BlockSpec((1, NUM_ATTRS, capacity), lambda t, cnt: (t, 0, 0))],
        out_specs=(
            pl.BlockSpec((1, OUT_ROWS, PIX), lambda t, cnt: (t, 0, 0)),
            pl.BlockSpec((1, capacity, PIX), lambda t, cnt: (t, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((capacity, COLS), jnp.float32)],
    )
    out, stash = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((num_tiles, OUT_ROWS, PIX), jnp.float32),
            jax.ShapeDtypeStruct((num_tiles, capacity, PIX), jnp.float32),
        ),
        interpret=resolve_interpret(interpret),
    )(count.astype(jnp.int32), attrs)
    return (*_split_outputs(out), stash)


# ---------------------------------------------------------------------------
# WSU-scheduled forward: one program per balanced tile pair
# ---------------------------------------------------------------------------


def _sched_fwd_kernel(perm_ref, trips_ref, attrs_a_ref, attrs_b_ref,
                      out_ref, stash_ref, cols_ref,
                      *, grid_w: int, chunk: int, tiles: int):
    """One program = one balanced pair: slot 2p (heavy) then 2p+1 (light).

    The chunk loop is a ``fori_loop`` over the slot's *actual* trip count
    (subtile streaming), so a light tile's program retires after its last
    real chunk instead of ``pl.when``-skipping to capacity.  Chunks the trip
    bound drops would contribute exactly 0 (padded fragments carry
    ``present=0`` -> alpha 0), so outputs stay bit-identical to the
    raster-order kernel."""
    pair = pl.program_id(0)
    for j, attrs_ref in enumerate((attrs_a_ref, attrs_b_ref)):
        slot = 2 * pair + j
        # Stacked schedules hold global rows (view*T + tile); the in-view
        # tile id drives the pixel coords (identity when unbatched).
        _fwd_tile_loop(attrs_ref, cols_ref, stash_ref, out_ref, j,
                       perm_ref[slot] % tiles, trips_ref[slot], grid_w, chunk)


@functools.partial(
    jax.jit, static_argnames=("grid", "chunk", "interpret", "tiles_per_view"))
def tile_render_fwd_sched(
    attrs: jnp.ndarray,   # (T, 12, K) — or (B*T, 12, K) stacked views
    perm: jnp.ndarray,    # (S,) int32 schedule slots (S = 2 * ceil(T/2))
    trips: jnp.ndarray,   # (S,) int32 chunk trips per slot
    grid: TileGrid,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
    tiles_per_view: int | None = None,
):
    """WSU-scheduled forward.  Outputs are in **slot (schedule) order** —
    row ``i`` belongs to tile ``perm[i]``; gather with ``sched.inv`` to get
    tile order.  Returns (color (S,3,256), depth (S,256), final_T (S,256),
    stash (S,K,256)).

    For stacked multi-view batching pass per-view schedules concatenated
    with their perm entries offset by ``view * tiles_per_view`` (global
    attr rows); per-pair computation is bit-identical to separate calls."""
    num_tiles, num_attrs, capacity = attrs.shape
    slots = perm.shape[0]
    assert num_attrs == NUM_ATTRS and capacity % chunk == 0
    assert slots % 2 == 0 and slots >= num_tiles
    tiles = tiles_per_view or num_tiles
    assert num_tiles % tiles == 0, (num_tiles, tiles)
    num_pairs = slots // 2

    kernel = functools.partial(_sched_fwd_kernel, grid_w=grid.grid_w,
                               chunk=chunk, tiles=tiles)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_pairs,),
        in_specs=[
            pl.BlockSpec((1, NUM_ATTRS, capacity),
                         lambda p, perm, trips: (perm[2 * p], 0, 0)),
            pl.BlockSpec((1, NUM_ATTRS, capacity),
                         lambda p, perm, trips: (perm[2 * p + 1], 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((2, OUT_ROWS, PIX), lambda p, perm, trips: (p, 0, 0)),
            pl.BlockSpec((2, capacity, PIX), lambda p, perm, trips: (p, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((capacity, COLS), jnp.float32)],
    )
    out, stash = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((slots, OUT_ROWS, PIX), jnp.float32),
            jax.ShapeDtypeStruct((slots, capacity, PIX), jnp.float32),
        ),
        interpret=resolve_interpret(interpret),
    )(perm, trips, attrs, attrs)
    return (*_split_outputs(out), stash)
