"""SLAM throughput baseline: fused scan engine vs per-iteration loop.

Writes ``BENCH_slam.json`` with frames/sec, dispatches/frame and
syncs/frame for the quick synthetic scene (``backend=ref``), so later PRs
have a perf floor to beat.  Wall-clock on a CPU container is a weak proxy
for accelerator FPS — dispatches/frame and syncs/frame are the
hardware-independent quantities the fused engine actually removes.

Run:  PYTHONPATH=src python -m benchmarks.run --only slam_fps
  or: PYTHONPATH=src python -m benchmarks.bench_slam_fps [--out BENCH_slam.json]
"""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

import json

from benchmarks.common import emit, stamp
from repro.core.keyframes import KeyframePolicy
from repro.core.pruning import PruneConfig
from repro.obs import Stopwatch, Telemetry, latency_summary
from repro.slam.datasets import make_dataset
from repro.slam.session import SLAMConfig, run_sequence


def _measure(ds, fused: bool, prune: bool):
    cfg = SLAMConfig(
        iters_track=6, iters_map=10, capacity=2048, frag_capacity=96,
        backend="ref", keyframe=KeyframePolicy(kind="monogs", interval=4),
        prune=PruneConfig(k0=4, step_frac=0.08) if prune else None,
        fused=fused,
    )
    # Warm-up run compiles every bundle; the timed run measures the steady
    # state the dispatch/sync counts describe.  The timed run carries a
    # SlamScope sink (zero-overhead: same dispatches, bitwise-same outputs)
    # so the row gets a per-frame host-latency histogram, not just a mean.
    run_sequence(ds, cfg)
    tele = Telemetry.on(trace=False)
    sw = Stopwatch()
    res = run_sequence(ds, cfg, telemetry=tele)
    wall = sw.elapsed()
    frames = res.work.frames
    return {
        "frames": frames,
        "wall_s": round(wall, 3),
        "fps": round(frames / max(wall, 1e-9), 3),
        "dispatches_per_frame": round(res.dispatches / frames, 2),
        "syncs_per_frame": round(res.syncs / frames, 2),
        "frame_latency_ms": latency_summary(tele.registry),
        "ate_cm": round(res.ate * 100, 3),
        "psnr_db": round(res.mean_psnr, 3),
        "fragments": res.work.fragments,
        "pixels": res.work.pixels,
        "gauss_iters": res.work.gaussians_iters,
        "pruned": res.prune_removed,
    }


def run(quick: bool = True, out: str = "BENCH_slam.json"):
    ds = make_dataset("room0", num_frames=8 if quick else 20, height=64,
                      width=64, num_gaussians=1200, frag_capacity=96)
    report = {
        "scene": "room0-synthetic",
        "backend": "ref",
        "mode": "quick" if quick else "full",
        "engine_fused": _measure(ds, fused=True, prune=False),
        "engine_fused_rtgs": _measure(ds, fused=True, prune=True),
        "loop_per_iteration": _measure(ds, fused=False, prune=False),
    }
    f = report["engine_fused"]
    u = report["loop_per_iteration"]
    report["dispatch_reduction"] = round(
        u["dispatches_per_frame"] / max(f["dispatches_per_frame"], 1e-9), 2)
    report["sync_reduction"] = round(
        u["syncs_per_frame"] / max(f["syncs_per_frame"], 1e-9), 2)
    stamp(report, quick=quick, scene="room0")
    with open(out, "w") as fh:
        json.dump(report, fh, indent=2)
    emit("slam_fps/fused", 1e6 / max(f["fps"], 1e-9),
         f"fps={f['fps']};disp_per_frame={f['dispatches_per_frame']};"
         f"syncs_per_frame={f['syncs_per_frame']};ate_cm={f['ate_cm']};"
         f"psnr_db={f['psnr_db']}")
    emit("slam_fps/unfused", 1e6 / max(u["fps"], 1e-9),
         f"fps={u['fps']};disp_per_frame={u['dispatches_per_frame']};"
         f"syncs_per_frame={u['syncs_per_frame']};"
         f"dispatch_reduction={report['dispatch_reduction']}x;"
         f"sync_reduction={report['sync_reduction']}x")
    return report


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_slam.json")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true")
    mode.add_argument("--quick", action="store_true",
                      help="quick mode (the default; spelled out for CI smoke jobs)")
    args = ap.parse_args()
    run(quick=not args.full, out=args.out)
