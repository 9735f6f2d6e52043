"""Pallas TPU backward rasterizer (Step 4: Rendering BP) with R&B-Buffer reuse.

The paper's key observation: alpha-gradient computing dominates Rendering BP
because the baseline *recomputes* alpha and transmittance (Eq. 5's divisions)
that the forward pass already produced. RTGS's R&B Buffer stashes them.

Here the stash is the forward kernel's ``stash`` output (raw per-fragment
alphas, resident in VMEM per tile block). The backward **never evaluates
exp and never divides by (1 - alpha)**: two multiply-only replays of the
blend chain reconstruct transmittance and the suffix sums.

  pass A:  total_ws = sum_k w_k s_k,  final_T          (forward replay)
  pass B:  dL/dalpha_k = Texc_k s_k
                     - (S_k + final_T gT) / (1 - am_k)  with
           S_k = total_ws - prefix_k   (suffix via prefix, no back-to-front
                                        divisions — Eq. 5 eliminated)

where s_k = gC . c_k + gD d_k is the fragment's blend-weight cotangent.

The per-pixel fragment gradients are reduced over the tile's 256 pixels
*inside* the kernel (a (10, K) register carry) — this is **GMU level 1**: the
(tile, gaussian) gradient leaves the kernel already merged, shrinking the
downstream scatter by 256x. Level 2 (tile -> Gaussian) happens outside in
``gmu.segment_merge``.

The single division by (1 - am_k) above is the analytic d/dam of the
*downstream* product — it is mathematically required by the chain rule
(also present in the ASIC's RBC), not an alpha recompute; am <= 0.99 keeps
it well-conditioned.

``tile_render_bwd_sched`` replays the **same WSU schedule** as the scheduled
forward (see repro/core/schedule.py): one program per balanced tile pair,
the permutation consumed via scalar prefetch, chunk loops bounded by the
slot's actual trip count, and the stash consumed directly in slot order —
the R&B buffer never has to be un-permuted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sorting import TileGrid
from repro.kernels import resolve_interpret
from repro.kernels.ref import ALPHA_MAX, NUM_ATTRS, PIX, TERM_EPS
from repro.kernels.tile_render import (
    COLS,
    DEFAULT_CHUNK,
    OUT_ROWS,
    _chunk_columns,
    _load_columns,
    _pixel_coords,
)

NUM_GRADS = 10  # mu_x, mu_y, conic_a, conic_b, conic_c, r, g, b, opacity, depth


def _pass_a_chunk(blk, alpha, chunk, g_r, g_g, g_b, g_d, carry):
    """Multiply-only forward replay over one chunk: accumulates total_ws and
    advances transmittance.  Shared op-for-op by both backward kernels."""
    trans, total_ws = carry
    for i in range(chunk):
        a = alpha[i:i + 1, :]
        f = blk[i:i + 1, :]
        include = (trans > TERM_EPS).astype(jnp.float32)
        am = a * include
        w = trans * am
        s = (g_r * f[:, 5:6] + g_g * f[:, 6:7]
             + g_b * f[:, 7:8] + g_d * f[:, 9:10])
        total_ws += w * s
        trans = trans * (1.0 - am)
    return trans, total_ws


def _pass_b_chunk(blk, alpha, start, chunk, px, py, g_r, g_g, g_b, g_d,
                  total_ws, ft_gt, frag_lane, carry):
    """Fragment gradients over one chunk, merged over the 256 pixels (GMU
    level 1) into column ``k`` of the (10, K) gradient carry.  Shared by
    both kernels."""
    trans, prefix, grads = carry
    for i in range(chunk):
        a = alpha[i:i + 1, :]
        f = blk[i:i + 1, :]
        include = (trans > TERM_EPS).astype(jnp.float32)
        am = a * include
        w = trans * am
        s = g_r * f[:, 5:6] + g_g * f[:, 6:7] + g_b * f[:, 7:8] + g_d * f[:, 9:10]
        prefix += w * s
        suffix = total_ws - prefix          # sum_{j>k} w_j s_j
        dam = trans * s - (suffix + ft_gt) / (1.0 - am)
        da = dam * include                  # (1,256)

        # chain to conic / position / opacity (clip + cutoff masks).
        o = f[:, 8:9]
        clip = (a < ALPHA_MAX).astype(jnp.float32)
        dq = da * (-0.5 * a) * clip         # d alpha/d q = -0.5 o G
        dx = px - f[:, 0:1]
        dy = py - f[:, 1:2]
        ca, cb, cc = f[:, 2:3], f[:, 3:4], f[:, 4:5]

        # GMU level 1: reduce each fragment gradient over 256 pixels, then
        # drop the (10, 1) column into lane k of the carry (a select, so
        # every other column passes through bit-untouched).
        rows = jnp.concatenate([
            dq * (-2.0) * (ca * dx + cb * dy),
            dq * (-2.0) * (cb * dx + cc * dy),
            dq * dx * dx,
            dq * 2.0 * dx * dy,
            dq * dy * dy,
            w * g_r,
            w * g_g,
            w * g_b,
            da * (a / jnp.maximum(o, 1e-12)) * clip,
            w * g_d,
        ], axis=0)                          # (10,256)
        col = jnp.sum(rows, axis=1, keepdims=True)
        grads = jnp.where(frag_lane == start + i, col, grads)

        trans = trans * (1.0 - am)
    return trans, prefix, grads


def _bwd_tile_loops(attrs_ref, cols_ref, stash_ref, g_ref, grads_ref, row,
                    tile_id, trips, grid_w, chunk):
    """Both backward passes for one tile, chunk loops bounded by ``trips``
    (subtile streaming), then the tile's (10, K) gradient block.  Shared
    op-for-op by the raster-order and WSU-scheduled kernels so gradients
    stay bit-identical between them."""
    _load_columns(attrs_ref, cols_ref)
    px, py = _pixel_coords(tile_id, grid_w)
    g = g_ref[row]                          # (5,256): r, g, b, depth, final T
    g_r, g_g, g_b, g_d, g_t = (g[i:i + 1, :] for i in range(OUT_ROWS))
    ones = jnp.ones((1, PIX), jnp.float32)
    zeros = jnp.zeros((1, PIX), jnp.float32)

    def chunk_inputs(start):
        blk = _chunk_columns(cols_ref, start, chunk)
        alpha = stash_ref[row, pl.ds(pl.multiple_of(start, chunk), chunk), :]
        return blk, alpha                   # (C,COLS), (C,256) R&B reuse

    # ---- pass A: total_ws and final transmittance (multiply-only replay) --
    def trip_a(c, carry):
        start = c * chunk

        def do_chunk(carry=carry):
            blk, alpha = chunk_inputs(start)
            return _pass_a_chunk(blk, alpha, chunk, g_r, g_g, g_b, g_d, carry)

        return jax.lax.cond(jnp.max(carry[0]) > TERM_EPS, do_chunk,
                            lambda carry=carry: carry)

    final_t, total_ws = jax.lax.fori_loop(0, trips, trip_a, (ones, zeros))
    ft_gt = final_t * g_t  # (1,256)

    # ---- pass B: fragment gradients, merged over pixels (GMU level 1) -----
    capacity = grads_ref.shape[-1]
    frag_lane = jax.lax.broadcasted_iota(jnp.int32, (NUM_GRADS, capacity), 1)

    def trip_b(c, carry):
        start = c * chunk

        def do_chunk(carry=carry):
            blk, alpha = chunk_inputs(start)
            return _pass_b_chunk(blk, alpha, start, chunk, px, py, g_r, g_g,
                                 g_b, g_d, total_ws, ft_gt, frag_lane, carry)

        return jax.lax.cond(jnp.max(carry[0]) > TERM_EPS, do_chunk,
                            lambda carry=carry: carry)

    grads0 = jnp.zeros((NUM_GRADS, capacity), jnp.float32)
    _, _, grads = jax.lax.fori_loop(0, trips, trip_b, (ones, zeros, grads0))
    grads_ref[row] = grads


def _cotangent_rows(g_color, g_depth, g_finalt):
    """Pixel cotangents as one (T, 5, 256) operand, rows as in the forward's
    output block."""
    return jnp.concatenate([g_color, g_depth[:, None], g_finalt[:, None]],
                           axis=1)


def _bwd_kernel(count_ref, attrs_ref, stash_ref, g_ref, grads_ref, cols_ref,
                *, grid_w: int, chunk: int, tiles: int):
    # Stacked multi-view grids run B*T programs; pixel coords use the
    # in-view tile id (identity when unbatched).
    t = pl.program_id(0)
    trips = (count_ref[t] + chunk - 1) // chunk
    _bwd_tile_loops(attrs_ref, cols_ref, stash_ref, g_ref, grads_ref, 0,
                    t % tiles, trips, grid_w, chunk)


@functools.partial(
    jax.jit, static_argnames=("grid", "chunk", "interpret", "tiles_per_view"))
def tile_render_bwd(
    attrs: jnp.ndarray,    # (T, 12, K) — or (B*T, 12, K) stacked views
    count: jnp.ndarray,    # (T,)
    stash: jnp.ndarray,    # (T, K, 256) forward alphas (the R&B buffer)
    g_color: jnp.ndarray,  # (T, 3, 256)
    g_depth: jnp.ndarray,  # (T, 256)
    g_finalt: jnp.ndarray,  # (T, 256)
    grid: TileGrid,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
    tiles_per_view: int | None = None,
) -> jnp.ndarray:
    """Returns per-(tile, fragment) merged gradients (T, 10, K).

    ``tiles_per_view`` = stacked-grid multi-view batching, see
    :func:`repro.kernels.tile_render.tile_render_fwd`."""
    num_tiles, num_attrs, capacity = attrs.shape
    assert num_attrs == NUM_ATTRS and capacity % chunk == 0
    tiles = tiles_per_view or num_tiles
    assert num_tiles % tiles == 0, (num_tiles, tiles)

    kernel = functools.partial(_bwd_kernel, grid_w=grid.grid_w, chunk=chunk,
                               tiles=tiles)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((1, NUM_ATTRS, capacity), lambda t, cnt: (t, 0, 0)),
            pl.BlockSpec((1, capacity, PIX), lambda t, cnt: (t, 0, 0)),
            pl.BlockSpec((1, OUT_ROWS, PIX), lambda t, cnt: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, NUM_GRADS, capacity), lambda t, cnt: (t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((capacity, COLS), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles, NUM_GRADS, capacity), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(count.astype(jnp.int32), attrs, stash,
      _cotangent_rows(g_color, g_depth, g_finalt))


# ---------------------------------------------------------------------------
# WSU-scheduled backward: replays the forward's pair schedule and stash
# ---------------------------------------------------------------------------


def _sched_bwd_kernel(perm_ref, trips_ref, attrs_a_ref, attrs_b_ref, stash_ref,
                      g_ref, grads_ref, cols_ref,
                      *, grid_w: int, chunk: int, tiles: int):
    pair = pl.program_id(0)
    for j, attrs_ref in enumerate((attrs_a_ref, attrs_b_ref)):
        slot = 2 * pair + j
        # Stacked schedules hold global rows (view*T + tile); pixel coords
        # use the in-view tile id (identity when unbatched).  Stash and
        # cotangent blocks are slot-ordered: row j belongs to this slot.
        _bwd_tile_loops(attrs_ref, cols_ref, stash_ref, g_ref, grads_ref, j,
                        perm_ref[slot] % tiles, trips_ref[slot], grid_w, chunk)


@functools.partial(
    jax.jit, static_argnames=("grid", "chunk", "interpret", "tiles_per_view"))
def tile_render_bwd_sched(
    attrs: jnp.ndarray,     # (T, 12, K) — or (B*T, 12, K) stacked views
    perm: jnp.ndarray,      # (S,) int32 schedule slots
    trips: jnp.ndarray,     # (S,) int32 chunk trips per slot
    stash: jnp.ndarray,     # (S, K, 256) forward alphas in SLOT order
    g_color: jnp.ndarray,   # (S, 3, 256) cotangents in SLOT order
    g_depth: jnp.ndarray,   # (S, 256)
    g_finalt: jnp.ndarray,  # (S, 256)
    grid: TileGrid,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
    tiles_per_view: int | None = None,
) -> jnp.ndarray:
    """Scheduled Rendering BP.  The stash and the pixel cotangents arrive in
    slot order (the stash straight from ``tile_render_fwd_sched``, the
    cotangents gathered with ``sched.perm``); the per-fragment gradients
    return in slot order (S, 10, K) — gather with ``sched.inv`` before the
    GMU level-2 merge so the merge sees tile order and stays bit-identical
    to the unscheduled path."""
    num_tiles, num_attrs, capacity = attrs.shape
    slots = perm.shape[0]
    assert num_attrs == NUM_ATTRS and capacity % chunk == 0
    assert slots % 2 == 0 and slots >= num_tiles
    tiles = tiles_per_view or num_tiles
    assert num_tiles % tiles == 0, (num_tiles, tiles)
    num_pairs = slots // 2

    kernel = functools.partial(_sched_bwd_kernel, grid_w=grid.grid_w,
                               chunk=chunk, tiles=tiles)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_pairs,),
        in_specs=[
            pl.BlockSpec((1, NUM_ATTRS, capacity),
                         lambda p, perm, trips: (perm[2 * p], 0, 0)),
            pl.BlockSpec((1, NUM_ATTRS, capacity),
                         lambda p, perm, trips: (perm[2 * p + 1], 0, 0)),
            pl.BlockSpec((2, capacity, PIX), lambda p, perm, trips: (p, 0, 0)),
            pl.BlockSpec((2, OUT_ROWS, PIX), lambda p, perm, trips: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, NUM_GRADS, capacity),
                               lambda p, perm, trips: (p, 0, 0)),
        scratch_shapes=[pltpu.VMEM((capacity, COLS), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, NUM_GRADS, capacity), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(perm, trips, attrs, attrs, stash,
      _cotangent_rows(g_color, g_depth, g_finalt))
