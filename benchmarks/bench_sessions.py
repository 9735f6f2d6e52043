"""Multi-session SLAM serving: S stacked sessions vs S independent loops.

The system-level redundancy RTGS leaves on the table is one host loop + one
dispatch stream *per sequence*.  SlamSession v1's ``step_many`` amortizes
one compiled step across S concurrent sequences: ONE executable, ONE
dispatch per frame-step, regardless of S.  This benchmark measures exactly
that — dispatches/frame-step and syncs/frame for S ∈ {1, 2, 4, 8} stacked
sessions against S independent solo session loops — and appends a
``"sessions"`` row to ``BENCH_slam.json``.

The serving claim the numbers back: dispatches per frame-step stay flat
(1.0) as S grows, i.e. per-*stream* dispatch cost falls 1/S, while each
stream's outputs remain bitwise-equal to its solo run
(tests/test_session.py).

Run:  PYTHONPATH=src python -m benchmarks.run --only sessions
  or: PYTHONPATH=src python -m benchmarks.bench_sessions [--quick]
"""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

import json
import os

import jax

from benchmarks.common import emit, stamp
from repro.core.keyframes import KeyframePolicy
from repro.obs import Stopwatch, Telemetry, latency_summary
from repro.slam.datasets import make_dataset, registered_scenes
from repro.slam.engine import EngineStats
from repro.slam.session import (
    SLAMConfig,
    SessionPool,
    session_init,
    session_step,
)


def _cfg():
    return SLAMConfig(
        iters_track=3, iters_map=4, capacity=1024, frag_capacity=48,
        map_window=2, scan_unroll=1,
        keyframe=KeyframePolicy(kind="monogs", interval=3),
    )


def _datasets(s, num_frames):
    names = registered_scenes()
    return [make_dataset(names[i % len(names)], num_frames=num_frames,
                         height=48, width=64, num_gaussians=400,
                         frag_capacity=48, seed=i) for i in range(s)]


def _measure(s: int, num_frames: int):
    cfg = _cfg()
    dss = _datasets(s, num_frames)
    steps = num_frames - 1

    # -- stacked: one pool, one dispatch per frame-step -------------------
    init_stats = EngineStats()
    pool = SessionPool([session_init(ds, cfg, stats=init_stats)
                        for ds in dss])
    # warm-up epoch compiles the S-stack executable; re-admit fresh
    # sessions and time the steady state (the convention of bench_slam_fps)
    for t in range(1, num_frames):
        pool.step([ds.frames[t] for ds in dss])
    for slot, ds in enumerate(dss):
        pool.swap(slot, session_init(ds, cfg))
    pool.stats = EngineStats()
    tele = Telemetry.on(trace=False)
    run_sw = Stopwatch()
    for t in range(1, num_frames):
        sw = Stopwatch()
        pool.step([ds.frames[t] for ds in dss])
        # host-side enqueue latency per stacked frame-step (the dispatch is
        # async — device time shows up only at the block below)
        tele.latency("step_host_ms", sw.elapsed() * 1e3)
    # dispatches are async: block on the final state so the wall clock
    # covers the compute, not just the enqueues
    jax.block_until_ready(jax.tree.leaves(pool.stacked))
    wall = run_sw.elapsed()
    fins = [pool.finalize(i, gt_w2c=[f.w2c_gt for f in dss[i].frames])
            for i in range(s)]
    stacked = {
        "sessions": s,
        "frame_steps": steps,
        "wall_s": round(wall, 3),
        "frames_per_s": round(s * steps / max(wall, 1e-9), 3),
        "dispatches_per_frame_step": round(pool.stats.dispatches / steps, 3),
        "dispatches_per_stream_frame": round(
            pool.stats.dispatches / (s * steps), 3),
        "syncs_per_frame_step": round(pool.stats.syncs / steps, 3),
        "step_host_ms": latency_summary(tele.registry, "step_host_ms"),
        "ate_cm": [round(f.ate * 100, 2) for f in fins],
        "psnr_db": [round(f.mean_psnr, 2) for f in fins],
    }

    # -- baseline: S independent solo step loops, measured symmetrically --
    # (init outside the timer, step dispatches only — same protocol as the
    # stacked measurement, so the comparison isolates the amortization:
    # S dispatches per frame-step solo vs 1 stacked)
    warm = [session_init(ds, cfg) for ds in dss]
    for t in range(1, num_frames):
        for i, ds in enumerate(dss):
            warm[i], _ = session_step(warm[i], ds.frames[t])
    solos = [session_init(ds, cfg) for ds in dss]
    solo_stats = EngineStats()
    solo_sw = Stopwatch()
    for t in range(1, num_frames):
        for i, ds in enumerate(dss):
            solos[i], _ = session_step(solos[i], ds.frames[t],
                                       stats=solo_stats)
    jax.block_until_ready([jax.tree.leaves(sess) for sess in solos])
    wall = solo_sw.elapsed()
    solo = {
        "wall_s": round(wall, 3),
        "frames_per_s": round(s * steps / max(wall, 1e-9), 3),
        "dispatches_per_frame_step": round(solo_stats.dispatches / steps, 3),
        "syncs_per_frame_step": round(solo_stats.syncs / steps, 3),
    }
    return {"stacked": stacked, "solo_loops": solo}


def run(quick: bool = True, out: str = "BENCH_slam.json"):
    sizes = (1, 2, 4, 8)
    num_frames = 4 if quick else 8
    rows = {}
    for s in sizes:
        rows[f"S{s}"] = _measure(s, num_frames)
        r = rows[f"S{s}"]
        emit(f"sessions/S{s}",
             1e6 / max(r["stacked"]["frames_per_s"], 1e-9),
             f"disp_per_step={r['stacked']['dispatches_per_frame_step']};"
             f"disp_per_stream_frame="
             f"{r['stacked']['dispatches_per_stream_frame']};"
             f"solo_disp_per_step={r['solo_loops']['dispatches_per_frame_step']};"
             f"syncs_per_step={r['stacked']['syncs_per_frame_step']}")

    d1 = rows["S1"]["stacked"]["dispatches_per_frame_step"]
    d4 = rows["S4"]["stacked"]["dispatches_per_frame_step"]
    summary = {
        "mode": "quick" if quick else "full",
        "scene_hw": [48, 64],
        "s4_vs_s1_dispatch_ratio": round(d4 / max(d1, 1e-9), 3),
        "rows": rows,
    }
    assert summary["s4_vs_s1_dispatch_ratio"] <= 1.25, (
        "S=4 stacked serving must not cost more dispatches/frame-step than "
        f"1.25x the S=1 value (got {summary['s4_vs_s1_dispatch_ratio']}x)")

    # Amend (don't clobber) the slam_fps/wsu report.
    report = {}
    if os.path.exists(out):
        with open(out) as fh:
            report = json.load(fh)
    report["sessions"] = stamp(summary, quick=quick)
    with open(out, "w") as fh:
        json.dump(report, fh, indent=2)
    return summary


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_slam.json")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true")
    mode.add_argument("--quick", action="store_true",
                      help="quick mode (the default; spelled out for CI "
                           "smoke jobs)")
    args = ap.parse_args()
    run(quick=not args.full, out=args.out)
