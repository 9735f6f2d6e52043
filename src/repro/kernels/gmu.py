"""Gradient Merging Unit (GMU): hierarchical gradient aggregation.

GPU 3DGS accumulates per-fragment Gaussian gradients with atomic adds that
serialize under collisions; RTGS inserts a Benes-network + bypass adder tree
that merges same-address gradients before they touch memory. TPU/XLA has no
atomics — an unsorted ``scatter-add`` is the analogue, and XLA serializes it
the same way. Our adaptation keeps the paper's hierarchy:

  level 1 (pixel -> tile):     inside ``tile_render_bp`` — the 256 per-pixel
                               fragment gradients are reduced in VMEM, so each
                               (tile, gaussian) pair emits ONE row (256x fewer
                               scatter operands).
  level 2 (tile -> Gaussian):  here — sort rows by Gaussian id, run-reduce
                               with dense prefix sums (VPU-friendly), and
                               scatter only run boundaries: at most two writes
                               per *unique* Gaussian instead of one per row
                               (the paper's "fully aggregated -> evictable"
                               entry becomes "closed run -> single write").

``segment_merge_scatter`` is the flat atomic-analogue baseline used by the
ablation benchmark (paper reports 68% merge-latency reduction; we report the
scatter-operand reduction, the quantity that latency is made of).

``block_cumsum`` is the Pallas building block: a carried blocked prefix sum
over the sorted rows (the pipelined adder tree with its stage queue).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def segment_merge_scatter(vals: jnp.ndarray, ids: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    """Baseline: flat unsorted scatter-add (GPU atomic-add analogue).

    vals: (M, G); ids: (M,) int32 with -1 for padding. Returns (N, G).
    """
    ok = ids >= 0
    safe = jnp.where(ok, ids, 0)
    contrib = jnp.where(ok[:, None], vals, 0.0)
    return jax.ops.segment_sum(contrib, safe, num_segments=num_segments)


def _cumsum_axis0(x: jnp.ndarray) -> jnp.ndarray:
    """Log-step inclusive prefix sum along axis 0 (Mosaic-friendly shifts)."""
    n = x.shape[0]
    shift = 1
    while shift < n:
        pad = jnp.zeros((shift,) + x.shape[1:], x.dtype)
        x = x + jnp.concatenate([pad, x[:-shift]], axis=0)
        shift *= 2
    return x


def _block_cumsum_kernel(vals_ref, out_ref, carry_ref, *, block: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = vals_ref[0]                      # (block, G)
    pref = _cumsum_axis0(x) + carry_ref[...]
    out_ref[0] = pref
    carry_ref[...] = pref[block - 1][None, :]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_cumsum(vals: jnp.ndarray, block: int = 256,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Pallas carried blocked prefix sum along axis 0 of (M, G).

    The grid runs sequentially on a TPU core; the carry lives in VMEM scratch
    and flows block-to-block (pipelined aggregation, the GMU's stage queue).
    """
    m, g = vals.shape
    assert m % block == 0, f"rows {m} must be a multiple of block {block}"
    grid = m // block
    return pl.pallas_call(
        functools.partial(_block_cumsum_kernel, block=block),
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, block, g), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, block, g), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid, block, g), vals.dtype),
        scratch_shapes=[pltpu.VMEM((1, g), vals.dtype)],
        interpret=resolve_interpret(interpret),
    )(vals.reshape(grid, block, g)).reshape(m, g)


@functools.partial(jax.jit, static_argnames=("num_segments", "use_pallas", "interpret"))
def segment_merge(
    vals: jnp.ndarray,
    ids: jnp.ndarray,
    num_segments: int,
    use_pallas: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """GMU level 2: sorted run-reduction merge.

    vals: (M, G) float32; ids: (M,) int32, -1 = padding. Returns (N, G).

    For sorted ids, the run of id x spans [s, e]; its sum is
    ``pref[e] - pref_excl[s]`` with pref the inclusive prefix sum. We scatter
    ``+pref`` at run ends and ``-pref_excl`` at run starts — boundary rows
    only, so scatter traffic scales with unique Gaussians, not fragments.
    """
    m, g = vals.shape
    ok = ids >= 0
    sort_keys = jnp.where(ok, ids, num_segments)  # padding sorts to the end
    order = jnp.argsort(sort_keys)
    ids_s = sort_keys[order]
    vals_s = jnp.where((ids_s < num_segments)[:, None], vals[order], 0.0)

    if use_pallas:
        pad = (-m) % 256
        padded = jnp.concatenate([vals_s, jnp.zeros((pad, g), vals.dtype)])
        pref = block_cumsum(padded, block=256, interpret=interpret)[:m]
    else:
        pref = jnp.cumsum(vals_s, axis=0)
    pref_excl = pref - vals_s

    is_start = jnp.concatenate([jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]])
    is_end = jnp.concatenate([ids_s[:-1] != ids_s[1:], jnp.ones((1,), bool)])
    valid = ids_s < num_segments

    out = jnp.zeros((num_segments, g), vals.dtype)
    end_ids = jnp.where(is_end & valid, ids_s, num_segments)
    start_ids = jnp.where(is_start & valid, ids_s, num_segments)
    out = out.at[end_ids].add(jnp.where((is_end & valid)[:, None], pref, 0.0), mode="drop")
    out = out.at[start_ids].add(
        jnp.where((is_start & valid)[:, None], -pref_excl, 0.0), mode="drop"
    )
    return out


def scatter_operand_counts(ids: jnp.ndarray, num_segments: int) -> dict:
    """Instrumentation for the GMU ablation: how many scatter operands the
    flat baseline vs. the merged path would issue (paper Fig. analog)."""
    ok = ids >= 0
    flat = int(jnp.sum(ok))
    sorted_ids = jnp.sort(jnp.where(ok, ids, num_segments))
    uniq = int(jnp.sum((sorted_ids[1:] != sorted_ids[:-1]) & (sorted_ids[1:] < num_segments)))
    uniq += int(sorted_ids[0] < num_segments)
    return {"flat_scatter_operands": flat, "merged_scatter_operands": 2 * uniq,
            "unique_gaussians": uniq}
