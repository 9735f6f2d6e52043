"""Fig. 14(b)/17 analogue: per-technique contribution breakdown.

No cycle-accurate GPU here, so each technique is measured in the quantity it
actually reduces (the paper's speedups are these quantities times hardware
constants):

  R&B buffer      — backward-pass HLO FLOPs + transcendentals with the stash
                    (``pallas``) vs alpha-recompute (``pallas_norb``)
  GMU             — scatter operands, flat vs hierarchically merged
  early termination — fragments actually blended vs fragments listed
  adaptive pruning  — Gaussian-iterations, before vs after
  dynamic downsampling — pixels rendered, before vs after
"""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.analysis.hlo_counter import analyze
from repro.core.downsample import DownsampleConfig
from repro.core.keyframes import KeyframePolicy
from repro.core.pruning import PruneConfig
from repro.kernels import gmu, ops, ref
from repro.slam.datasets import make_dataset
from repro.slam.session import SLAMConfig, run_sequence


def _scene(num_frames=8):
    return make_dataset("room0", num_frames=num_frames, height=64, width=64,
                        num_gaussians=1500, frag_capacity=96)


def rb_buffer_flops(scene):
    """Backward FLOPs with/without the R&B stash (the 20->4 cycle claim)."""
    from repro.core.projection import project
    from repro.core.camera import Camera
    from repro.core.sorting import build_fragment_lists, make_tile_grid

    f0 = scene.frames[0]
    from repro.slam.session import _seed_map, SLAMConfig as SC

    g = _seed_map(scene, SC(capacity=2048, frag_capacity=96))
    grid = make_tile_grid(64, 64)
    cam = Camera(scene.intrinsics, jnp.asarray(f0.w2c_gt))
    proj = project(g, cam)
    frags = build_fragment_lists(proj, grid, 96)
    target = jnp.asarray(f0.rgb)

    from repro.core.raster_api import RasterInputs, RasterPlan

    results = {}
    for backend in ("pallas", "pallas_norb"):
        plan = RasterPlan(grid=grid, backend=backend, capacity=96)

        def loss(mu2d, conic, color, opacity, depth, plan=plan):
            img, dep, ft = ops.rasterize(
                RasterInputs(mu2d=mu2d, conic=conic, color=color,
                             opacity=opacity, depth=depth, frags=frags),
                plan,
            )
            return jnp.mean((img - target) ** 2)

        lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            proj.mu2d, proj.conic, proj.color, proj.opacity, proj.depth
        )
        r = analyze(lowered.compile().as_text())
        results[backend] = r
    return results


def run(quick: bool = True):
    scene = _scene(8 if quick else 16)

    # --- R&B buffer: BP transcendental + flop reduction ---------------------
    rb = rb_buffer_flops(scene)
    base_t = rb["pallas_norb"]["transcendentals"]
    ours_t = rb["pallas"]["transcendentals"]
    emit("fig17/rb_buffer", 0.0,
         f"bp_transcendentals_recompute={base_t:.3g};with_stash={ours_t:.3g};"
         f"reduction={base_t / max(ours_t, 1):.2f}x;"
         f"bp_flops_recompute={rb['pallas_norb']['flops']:.3g};"
         f"bp_flops_stash={rb['pallas']['flops']:.3g}")

    # --- GMU: scatter-operand reduction + wall time -------------------------
    from repro.core.projection import project
    from repro.core.camera import Camera
    from repro.core.sorting import build_fragment_lists, make_tile_grid
    from repro.slam.session import _seed_map

    g = _seed_map(scene, SLAMConfig(capacity=2048, frag_capacity=96))
    grid = make_tile_grid(64, 64)
    proj = project(g, Camera(scene.intrinsics, jnp.asarray(scene.frames[0].w2c_gt)))
    frags = build_fragment_lists(proj, grid, 96)
    ids = frags.idx.reshape(-1)
    stats = gmu.scatter_operand_counts(ids, g.capacity)
    vals = jax.random.normal(jax.random.PRNGKey(0), (ids.shape[0], 10))
    t_flat = timeit(jax.jit(lambda v, i: gmu.segment_merge_scatter(v, i, g.capacity)), vals, ids)
    t_merge = timeit(jax.jit(lambda v, i: gmu.segment_merge(v, i, g.capacity)), vals, ids)
    emit("fig17/gmu_merge", t_merge,
         f"flat_us={t_flat:.1f};merged_us={t_merge:.1f};"
         f"flat_operands={stats['flat_scatter_operands']};"
         f"merged_operands={stats['merged_scatter_operands']};"
         f"operand_reduction={stats['flat_scatter_operands'] / max(stats['merged_scatter_operands'],1):.2f}x")

    # --- early termination: fragments blended vs listed ----------------------
    attrs = ops._pack_attrs(proj.mu2d, proj.conic, proj.color, proj.opacity,
                            proj.depth, frags.idx)
    alpha = ref.fragment_alphas(attrs, grid)
    texc = jnp.cumprod(1.0 - alpha, axis=-1)
    texc = jnp.concatenate([jnp.ones_like(texc[..., :1]), texc[..., :-1]], -1)
    listed = int(jnp.sum(frags.count)) * 256
    blended = int(jnp.sum((texc > ref.TERM_EPS) & (alpha > 0)))
    emit("fig17/early_termination", 0.0,
         f"fragxpix_listed={listed};fragxpix_blended={blended};"
         f"skip_fraction={1 - blended / max(listed, 1):.3f}")

    # --- algorithm techniques: work reduction --------------------------------
    base = run_sequence(scene, SLAMConfig(
        iters_track=6, iters_map=10, capacity=3072, frag_capacity=96,
        keyframe=KeyframePolicy(kind="monogs", interval=4)))
    prune_only = run_sequence(scene, SLAMConfig(
        iters_track=6, iters_map=10, capacity=3072, frag_capacity=96,
        keyframe=KeyframePolicy(kind="monogs", interval=4),
        prune=PruneConfig(k0=4, step_frac=0.1)))
    down_only = run_sequence(scene, SLAMConfig(
        iters_track=6, iters_map=10, capacity=3072, frag_capacity=96,
        keyframe=KeyframePolicy(kind="monogs", interval=4),
        downsample=DownsampleConfig(enabled=True)))
    emit("fig17/adaptive_pruning", 0.0,
         f"gauss_iters_base={base.work.gaussians_iters};"
         f"gauss_iters_pruned={prune_only.work.gaussians_iters};"
         f"reduction={base.work.gaussians_iters / max(prune_only.work.gaussians_iters,1):.2f}x")
    emit("fig17/dynamic_downsampling", 0.0,
         f"pixels_base={base.work.pixels};pixels_down={down_only.work.pixels};"
         f"reduction={base.work.pixels / max(down_only.work.pixels,1):.2f}x;"
         f"fragments_base={base.work.fragments};fragments_down={down_only.work.fragments}")

    # --- fused engine: dispatch/sync + wall-time before/after ---------------
    import time

    small = _scene(6)
    cfg_kw = dict(iters_track=6, iters_map=10, capacity=3072, frag_capacity=96,
                  keyframe=KeyframePolicy(kind="monogs", interval=4))
    for fused in (True, False):
        run_sequence(small, SLAMConfig(fused=fused, **cfg_kw))  # compile
    t0 = time.time()
    fused_res = run_sequence(small, SLAMConfig(fused=True, **cfg_kw))
    t_fused = time.time() - t0
    t0 = time.time()
    loop_res = run_sequence(small, SLAMConfig(fused=False, **cfg_kw))
    t_loop = time.time() - t0
    nf = fused_res.work.frames
    emit("fig17/fused_engine", t_fused * 1e6 / nf,
         f"disp_per_frame_fused={fused_res.dispatches / nf:.1f};"
         f"disp_per_frame_loop={loop_res.dispatches / nf:.1f};"
         f"syncs_per_frame_fused={fused_res.syncs / nf:.1f};"
         f"syncs_per_frame_loop={loop_res.syncs / nf:.1f};"
         f"wall_fused_s={t_fused:.2f};wall_loop_s={t_loop:.2f};"
         f"dispatch_reduction={loop_res.dispatches / max(fused_res.dispatches,1):.2f}x")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    run(quick=False)
