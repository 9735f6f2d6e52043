"""End-to-end 3DGS-SLAM with RTGS's multi-level redundancy reduction.

Runs MonoGS-style SLAM (tracking + keyframe mapping) on a synthetic RGB-D
room, once as the base algorithm and once with RTGS (adaptive Gaussian
pruning §4.1 + dynamic downsampling §4.2), and prints the paper-style
comparison: ATE, PSNR, work reduction.

Run:  PYTHONPATH=src python examples/slam_demo.py [--frames 20]
"""

import argparse

from repro.core.downsample import DownsampleConfig
from repro.core.keyframes import KeyframePolicy
from repro.core.pruning import PruneConfig
from repro.core.raster_api import registered_backends
from repro.obs import Telemetry, TraceRecorder, latency_summary
from repro.slam.datasets import make_dataset
from repro.slam.session import SLAMConfig, run_sequence


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=14)
    ap.add_argument("--scene", default="room0")
    ap.add_argument("--backend", default="ref", choices=registered_backends(),
                    help="rasterizer backend (any registered RasterAPI "
                         "backend; 'ref' is fastest on CPU, 'schedule' runs "
                         "the WSU-scheduled Pallas kernels)")
    ap.add_argument("--unfused", action="store_true",
                    help="per-iteration loop instead of the scan-fused "
                         "engine (the seed's dispatch pattern)")
    ap.add_argument("--trace", default="", metavar="out.json",
                    help="export a SlamScope Chrome-trace JSON of both runs "
                         "(open in Perfetto: ui.perfetto.dev)")
    args = ap.parse_args()
    # One trace file spans both variants; each gets its own registry so the
    # base/rtgs latency histograms stay separate.
    trace = TraceRecorder(enabled=bool(args.trace))

    print(f"generating synthetic dataset '{args.scene}' ({args.frames} frames)…")
    ds = make_dataset(args.scene, num_frames=args.frames, height=64, width=128,
                      num_gaussians=2000, frag_capacity=96)

    results = {}
    for variant in ("base", "rtgs"):
        cfg = SLAMConfig(
            base_algo="monogs",
            keyframe=KeyframePolicy(kind="monogs", interval=4),
            iters_track=10, iters_map=16,
            capacity=4096, frag_capacity=96,
            backend=args.backend,
            prune=PruneConfig(k0=5, step_frac=0.08) if variant == "rtgs" else None,
            downsample=DownsampleConfig(enabled=(variant == "rtgs")),
            fused=not args.unfused,
        )
        print(f"\nrunning {variant} ({'per-iteration' if args.unfused else 'scan-fused'} engine)…")
        tele = Telemetry(trace=trace)
        res = run_sequence(ds, cfg, verbose=True, telemetry=tele)
        results[variant] = res
        nf = res.work.frames
        lat = latency_summary(tele.registry, stream=ds.name)
        print(f"  ATE {res.ate*100:6.2f} cm | PSNR {res.mean_psnr:5.2f} dB | "
              f"{res.wall_time_s:5.1f}s | pruned {res.prune_removed} | "
              f"{res.dispatches / nf:.1f} dispatches/frame | "
              f"{res.syncs / nf:.1f} syncs/frame | frame p50/p99 "
              f"{lat.get('p50_ms', 0):.1f}/{lat.get('p99_ms', 0):.1f} ms")

    b, r = results["base"], results["rtgs"]
    print("\n=== RTGS vs base (paper Tab. 6 shape) ===")
    print(f"ATE:        {b.ate*100:6.2f} -> {r.ate*100:6.2f} cm")
    print(f"PSNR:       {b.mean_psnr:6.2f} -> {r.mean_psnr:6.2f} dB")
    print(f"pixels:     {b.work.pixels:9d} -> {r.work.pixels:9d} "
          f"({b.work.pixels / max(r.work.pixels, 1):.2f}x fewer)")
    print(f"gauss-iters:{b.work.gaussians_iters:9d} -> {r.work.gaussians_iters:9d} "
          f"({b.work.gaussians_iters / max(r.work.gaussians_iters, 1):.2f}x fewer)")
    print(f"fragments:  {b.work.fragments:9d} -> {r.work.fragments:9d}")
    if args.trace and trace.enabled:
        trace.export(args.trace)
        print(f"\ntrace: wrote {args.trace} (load at ui.perfetto.dev)")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
