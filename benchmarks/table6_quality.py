"""Tab. 6 analogue: base algorithms vs +RTGS on synthetic scenes.

Columns: ATE (cm), PSNR (dB), wall-FPS (CPU proxy), work reduction
(fragments + pixels + gaussian-iterations — the quantities the paper's GPU
FPS gains are made of; wall-clock on this container is a weak proxy since
the reference rasterizer is already vectorized batch compute)."""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

from benchmarks.common import emit
from repro.core.downsample import DownsampleConfig
from repro.core.keyframes import KeyframePolicy
from repro.core.pruning import PruneConfig
from repro.slam.datasets import make_dataset
from repro.slam.session import SLAMConfig, run_sequence

_POLICIES = {
    "gsslam": KeyframePolicy(kind="gsslam", trans_thresh=0.08, rot_thresh=0.08),
    "monogs": KeyframePolicy(kind="monogs", interval=4),
    "photoslam": KeyframePolicy(kind="photoslam", pho_thresh=0.04),
}


def run(quick: bool = True):
    scenes = ["room0"] if quick else ["room0", "room1"]
    n_frames = 12 if quick else 30
    for scene in scenes:
        ds = make_dataset(scene, num_frames=n_frames, height=64, width=64,
                          num_gaussians=1500, frag_capacity=96)
        for algo, policy in _POLICIES.items():
            for variant in ("base", "rtgs"):
                cfg = SLAMConfig(
                    base_algo=algo, keyframe=policy,
                    iters_track=8, iters_map=12,
                    capacity=3072, frag_capacity=96,
                    prune=PruneConfig(k0=5, step_frac=0.08) if variant == "rtgs" else None,
                    downsample=DownsampleConfig(enabled=(variant == "rtgs")),
                )
                res = run_sequence(ds, cfg)
                fps = res.work.frames / max(res.wall_time_s, 1e-9)
                emit(
                    f"table6/{scene}/{algo}/{variant}",
                    res.wall_time_s * 1e6 / res.work.frames,
                    f"ate_cm={res.ate*100:.2f};psnr_db={res.mean_psnr:.2f};"
                    f"fps={fps:.2f};fragments={res.work.fragments};"
                    f"pixels={res.work.pixels};gauss_iters={res.work.gaussians_iters};"
                    f"pruned={res.prune_removed};"
                    f"disp_per_frame={res.dispatches / res.work.frames:.1f}",
                )


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    run(quick=False)
