"""Tab. 7 analogue: SplaTAM (per-frame mapping, no keyframe policy) with and
without RTGS techniques — tracking-rate proxy and peak Gaussian count."""

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
    for variant in ("base", "rtgs"):
        cfg = SLAMConfig(
            base_algo="splatam", keyframe=KeyframePolicy(kind="splatam"),
            iters_track=6, iters_map=8, capacity=4096, frag_capacity=96,
            prune=PruneConfig(k0=5, step_frac=0.08) if variant == "rtgs" else None,
        )
        res = run_sequence(ds, cfg)
        emit(
            f"table7/splatam/{variant}",
            res.wall_time_s * 1e6 / res.work.frames,
            f"ate_cm={res.ate*100:.2f};psnr_db={res.mean_psnr:.2f};"
            f"peak_gaussians={max(res.alive_per_frame)};"
            f"gauss_iters={res.work.gaussians_iters};"
            f"disp_per_frame={res.dispatches / res.work.frames:.1f}",
        )


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    run(quick=False)
