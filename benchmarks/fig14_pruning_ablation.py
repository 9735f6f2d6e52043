"""Fig. 14(a) analogue: pruning-ratio sweep — ATE/PSNR vs prune cap.

The paper's finding: <=50% pruning keeps quality; >=60% degrades sharply.
"""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

from benchmarks.common import emit
from repro.core.keyframes import KeyframePolicy
from repro.core.pruning import PruneConfig
from repro.slam.datasets import make_dataset
from repro.slam.session import SLAMConfig, run_sequence


def run(quick: bool = True):
    ds = make_dataset("room0", num_frames=10 if quick else 24, height=64,
                      width=64, num_gaussians=1500, frag_capacity=96)
    ratios = [0.0, 0.3, 0.5] if quick else [0.0, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7]
    for ratio in ratios:
        cfg = SLAMConfig(
            iters_track=8, iters_map=12, capacity=3072, frag_capacity=96,
            keyframe=KeyframePolicy(kind="monogs", interval=4),
            prune=PruneConfig(k0=4, step_frac=0.15, max_ratio=ratio)
            if ratio > 0 else None,
        )
        res = run_sequence(ds, cfg)
        emit(
            f"fig14a/prune_cap_{int(ratio*100)}pct",
            res.wall_time_s * 1e6 / res.work.frames,
            f"ate_cm={res.ate*100:.2f};psnr_db={res.mean_psnr:.2f};"
            f"pruned={res.prune_removed};gauss_iters={res.work.gaussians_iters}",
        )


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    run(quick=False)
