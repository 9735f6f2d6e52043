"""Kernel micro-benchmarks: oracle-path wall time over tile/fragment sweeps
plus trip-count-aware FLOP/byte counts for the kernels' jitted wrappers
(interpret-mode Pallas timings are Python-loop noise, so the oracle carries
the wall-clock numbers; the HLO counts are backend-independent)."""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.analysis.hlo_counter import analyze
from repro.core.sorting import make_tile_grid
from repro.kernels import ref


def run(quick: bool = True):
    sweeps = [(64, 64, 64), (128, 128, 96)] if quick else [
        (64, 64, 64), (128, 128, 96), (128, 192, 128), (192, 256, 128),
    ]
    for h, w, cap in sweeps:
        grid = make_tile_grid(h, w)
        key = jax.random.PRNGKey(0)
        attrs = jax.random.uniform(key, (grid.num_tiles, 12, cap))
        attrs = attrs.at[:, 10].set(1.0)
        fwd = jax.jit(lambda a: ref.rasterize_tiles(a, grid))
        us = timeit(fwd, attrs)
        lowered = jax.jit(lambda a: ref.rasterize_tiles(a, grid)).lower(attrs)
        counts = analyze(lowered.compile().as_text())
        frag_pix = grid.num_tiles * 256 * cap
        emit(f"kernel/raster_fwd_{h}x{w}_K{cap}", us,
             f"fragpix={frag_pix};flops={counts['flops']:.3g};"
             f"ns_per_fragpix={us * 1e3 / frag_pix:.2f}")

        def loss(a):
            c, d, t = ref.rasterize_tiles(a, grid)
            return jnp.sum(c) + jnp.sum(d) + jnp.sum(t)

        bwd = jax.jit(jax.grad(loss))
        us_b = timeit(bwd, attrs)
        emit(f"kernel/raster_bwd_{h}x{w}_K{cap}", us_b,
             f"ns_per_fragpix={us_b * 1e3 / frag_pix:.2f}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    run(quick=False)
