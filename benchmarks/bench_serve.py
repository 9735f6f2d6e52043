"""SlamServe: device-sharded, queue-fed serving across D devices.

PR 4's ``step_many`` made S sessions cost ONE dispatch per frame-step on
one device; SlamServe shards those S session rows over a D-device "data"
mesh and feeds them through the asynchronous FrameQueue/SlamServer
pipeline.  This benchmark measures the serving tier per device count —
frames/s, dispatches and syncs per frame-step (the hardware-independent
metrics: on this container the "devices" are forced host-platform slices
of one CPU core, so wall clock does NOT improve with D), and mean queue
wait — and appends a ``"serve"`` row to ``BENCH_slam.json``.

On an accelerator every device count runs in this process over
``make_data_mesh(D)`` on the real devices (a chip belongs to one process,
so a child could not get the one the parent holds).  On the CPU the
"devices" need ``--xla_force_host_platform_device_count`` set before JAX
initializes, so each D runs in its own worker subprocess (the
tests/test_multidevice.py pattern) and the parent aggregates the workers'
JSON lines.

Run:  PYTHONPATH=src python -m benchmarks.run --only serve
  or: PYTHONPATH=src python -m benchmarks.bench_serve [--quick]
"""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

import argparse
import json
import os
import subprocess
import sys

_RESULT_TAG = "SERVE_RESULT "


def _measure(devices: int, sessions: int, num_frames: int,
             trace_out: str = "") -> dict:
    """Time one serving epoch of S streams through ShardedPool + SlamServer
    on the first D devices, with a SlamScope sink attached (the measured
    epoch is telemetry-on — the zero-overhead invariant means the numbers
    are the production numbers)."""
    import jax

    from repro.core.keyframes import KeyframePolicy
    from repro.launch.mesh import make_data_mesh
    from repro.obs import Stopwatch, Telemetry, latency_summary
    from repro.slam.datasets import make_dataset, registered_scenes
    from repro.slam.server import ShardedPool, SlamServer
    from repro.slam.session import SLAMConfig, session_init

    assert len(jax.devices()) >= devices, (len(jax.devices()), devices)
    cfg = SLAMConfig(iters_track=3, iters_map=4, capacity=1024,
                     frag_capacity=48, map_window=2, scan_unroll=1,
                     keyframe=KeyframePolicy(kind="monogs", interval=3))
    names = registered_scenes()
    dss = [make_dataset(names[i % len(names)], num_frames=num_frames,
                        height=48, width=64, num_gaussians=400,
                        frag_capacity=48, seed=i) for i in range(sessions)]
    steps = num_frames - 1

    def epoch(tele=None):
        pool = ShardedPool([session_init(ds, cfg) for ds in dss],
                           mesh=make_data_mesh(devices))
        srv = SlamServer(pool, queue_depth=2, telemetry=tele)
        sw = Stopwatch()
        for t in range(1, num_frames):
            for slot, ds in enumerate(dss):
                srv.submit(slot, ds.frames[t])
            srv.pump()          # async dispatch; staging overlaps compute
        srv.drain()             # the one sync
        return pool, srv, sw.elapsed()

    epoch()                     # warm-up epoch compiles the executables
    tele = Telemetry.on(trace=bool(trace_out))
    pool, srv, wall = epoch(tele)   # steady state, telemetry-on

    assert pool.stats.dispatches == steps, (pool.stats.dispatches, steps)
    run_syncs = pool.stats.syncs          # the drain (finalize fetches are
                                          # per-retiree, not per-run — keep
                                          # them out of the run metric)
    reg = tele.registry
    # Registry-side dispatch split must agree with the pool's own counters.
    assert reg.sum_counters("dispatches", kind="step") == steps
    fins = [pool.finalize(i, gt_w2c=[f.w2c_gt for f in dss[i].frames])
            for i in range(sessions)]
    for i, fin in enumerate(fins):        # already-fetched work → registry
        tele.work(f"s{i}", fin.work)
    work_per_stream = {
        f"s{i}": {f: reg.sum_counters(f"work/{f}", stream=f"s{i}")
                  for f in ("fragments", "pixels", "unstable_gaussians")}
        for i in range(sessions)}
    tele.export_trace(trace_out)
    return {
        "platform": jax.devices()[0].platform,
        "devices": devices,
        "sessions": sessions,
        "frame_steps": steps,
        "wall_s": round(wall, 3),
        "frames_per_s": round(sessions * steps / max(wall, 1e-9), 3),
        "dispatches_per_frame_step": round(pool.stats.dispatches / steps, 3),
        "syncs_per_frame_step": round(run_syncs / steps, 3),
        "syncs_per_run": run_syncs,
        "queue_wait_ms_per_frame": round(srv.stats.queue_wait_ms_per_frame, 3),
        "stage_s": round(srv.stats.stage_s, 3),
        # SlamScope registry summaries (merged across the S streams):
        "frame_latency_ms": latency_summary(reg, "frame_latency_ms"),
        "queue_wait_ms": latency_summary(reg, "queue_wait_ms"),
        "queue_depth_hwm": reg.max_gauge_hwm("queue_depth"),
        "admin_dispatches": reg.sum_counters("dispatches", kind="admin"),
        "work_per_stream": work_per_stream,
        "ate_cm": [round(f.ate * 100, 2) for f in fins],
        "psnr_db": [round(f.mean_psnr, 2) for f in fins],
    }


def _spawn(devices: int, sessions: int, num_frames: int,
           trace_out: str = "") -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serve", "--worker",
         "--devices", str(devices), "--sessions", str(sessions),
         "--frames", str(num_frames)]
        + (["--trace-out", trace_out] if trace_out else []),
        capture_output=True, text=True, env=env, timeout=1800,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"serve worker (D={devices}) failed:\n{out.stdout}\n"
            f"{out.stderr[-3000:]}")
    for line in out.stdout.splitlines():
        if line.startswith(_RESULT_TAG):
            return json.loads(line[len(_RESULT_TAG):])
    raise RuntimeError(f"serve worker (D={devices}) emitted no result line:"
                       f"\n{out.stdout}")


def run(quick: bool = True, out: str = "BENCH_slam.json",
        trace: bool = True):
    import jax

    from benchmarks.common import emit, stamp

    device_counts = (1, 2) if quick else (1, 2, 4)
    sessions = 4 if quick else 8
    num_frames = 4 if quick else 8
    # Real devices: measure in this process, over as many as exist.
    in_process = jax.default_backend() != "cpu"
    if in_process:
        device_counts = [d for d in device_counts if d <= jax.device_count()]

    rows = {}
    for d in device_counts:
        trace_out = f"bench_serve_trace_D{d}.json" if trace else ""
        measure = _measure if in_process else _spawn
        r = measure(d, sessions, num_frames, trace_out=trace_out)
        if trace_out:
            r["trace"] = trace_out
        rows[f"D{d}"] = r
        lat = r["frame_latency_ms"]
        emit(f"serve/D{d}",
             1e6 / max(r["frames_per_s"], 1e-9),
             f"disp_per_step={r['dispatches_per_frame_step']};"
             f"p50_ms={lat['p50_ms']};p99_ms={lat['p99_ms']};"
             f"qdepth_hwm={r['queue_depth_hwm']}")

    # The serving invariant: dispatches/frame-step == 1.0 for every device
    # count (each worker also asserts it in-process).
    for key, r in rows.items():
        assert r["dispatches_per_frame_step"] == 1.0, (key, r)

    summary = {
        "mode": "quick" if quick else "full",
        "scene_hw": [48, 64],
        "sessions": sessions,
        "dispatches_per_frame_step": 1.0,
        # Headline latency row (single-device serving, pool-merged):
        "frame_latency_ms": rows["D1"]["frame_latency_ms"],
        "queue_depth_hwm": max(r["queue_depth_hwm"] for r in rows.values()),
        "rows": rows,
    }

    # Amend (don't clobber) the slam_fps/wsu/sessions report.
    report = {}
    if os.path.exists(out):
        with open(out) as fh:
            report = json.load(fh)
    report["serve"] = stamp(summary, quick=quick)
    with open(out, "w") as fh:
        json.dump(report, fh, indent=2)
    return summary


# ---------------------------------------------------------------------------
# v2: continuous-batching mixed-rate scenario (SlamServe v2 scheduler)
# ---------------------------------------------------------------------------


def _class_latency(reg, name: str, prefix: str) -> dict:
    """Latency summary merged over every stream whose label starts with
    ``prefix`` (the fast/slow class split of the mixed-rate scenario)."""
    from repro.obs.registry import Histogram

    merged = None
    for labels, h in reg.series(name, kind="histogram"):
        if not str(labels.get("stream", "")).startswith(prefix):
            continue
        if merged is None:
            merged = Histogram(h.growth)
        merged.merge(h)
    if merged is None or merged.count == 0:
        return {"count": 0}
    return {"count": merged.count,
            "p50_ms": round(merged.quantile(0.50), 4),
            "p90_ms": round(merged.quantile(0.90), 4),
            "p99_ms": round(merged.quantile(0.99), 4),
            "mean_ms": round(merged.mean, 4),
            "max_ms": round(merged.max, 4)}


def _v1_baseline(dss: dict, period_s: dict, pool,
                 max_steps: int = 7) -> dict:
    """The lockstep-v1 baseline for the mixed-rate workload: the SAME
    streams through one fixed-width SlamServer over the ladder's widest
    (already-warmed) pool, fast and slow sharing each lockstep batch,
    served in admission waves.  A fast frame can only dispatch when every
    slow peer's next frame arrives — the head-of-line stall v2 exists to
    remove.  Uses its own registry so v2's histograms stay clean.
    ``max_steps`` caps each stream's fed frames: the lockstep per-frame
    wait is steady-state from frame 2 (every fast frame waits one slow
    period, forever), so longer streams only repeat the same sample
    while the SEQUENTIAL waves multiply wall time."""
    import time as _time

    from repro.obs import Stopwatch, Telemetry, now_s
    from repro.slam.server import SlamServer
    from repro.slam.session import session_init

    tele = Telemetry.on(trace=False)
    sids = list(dss)
    width = pool.size
    sw = Stopwatch()
    for wave_at in range(0, len(sids), width):
        wave = sids[wave_at:wave_at + width]
        srv = SlamServer(pool, queue_depth=2, live=[], telemetry=tele,
                         name="v1")
        slots = {sid: srv.admit(session_init(dss[sid]["ds"],
                                             dss[sid]["cfg"]), label=sid)
                 for sid in wave}
        pending = {sid: list(dss[sid]["ds"].frames[1:1 + max_steps])
                   for sid in wave}
        due = {sid: 0.0 for sid in wave}
        # Every stream has the same frame count, so the lockstep queues
        # drain together: the loop terminates without per-slot retire.
        while (any(pending.values())
               or any(srv.queue.fill(s) for s in slots.values())):
            now = now_s()
            for sid in wave:
                if pending[sid] and now >= due[sid]:
                    if srv.offer(slots[sid], pending[sid][0]):
                        pending[sid].pop(0)
                        due[sid] = now + period_s.get(sid, 0.0)
            if srv.pump() == 0:
                _time.sleep(2e-3)
        srv.drain()
    return {
        "wall_s": round(sw.elapsed(), 3),
        "queue_wait_ms": {
            "fast": _class_latency(tele.registry, "queue_wait_ms", "fast"),
            "slow": _class_latency(tele.registry, "queue_wait_ms", "slow")},
        "frame_latency_ms": {
            "fast": _class_latency(tele.registry, "frame_latency_ms", "fast"),
            "slow": _class_latency(tele.registry, "frame_latency_ms",
                                   "slow")},
    }


def run_v2(quick: bool = True, out: str = "BENCH_slam.json",
           trace: bool = True):
    """The SlamServe v2 mixed-rate scenario: 32 queued streams (half
    camera-rate-limited "slow", half unthrottled "fast") ingested by a
    producer thread through the S ∈ {2, 4, 8} pool-width ladder under the
    queue-depth/oldest-deadline scheduler, compared against the lockstep
    v1 baseline on the same workload.  Asserts the PR's acceptance gates
    in-process and appends a ``"serve_v2"`` row to ``BENCH_slam.json``."""
    import jax

    from benchmarks.common import emit, stamp
    from repro.core.keyframes import KeyframePolicy
    from repro.obs import Stopwatch, Telemetry, latency_summary
    from repro.slam.datasets import make_dataset, registered_scenes
    from repro.slam.engine import EngineStats
    from repro.slam.sched import (IngestWorker, PoolLadder, QueueDepthPolicy,
                                  SlamScheduler)
    from repro.slam.server import ServeStats, compile_cache_stats
    from repro.slam.session import SLAMConfig, session_init

    widths = (2, 4, 8)
    n_streams = 32
    # Streams long enough that the post-sort steady state (fast lanes
    # running clean) dominates each fast stream's latency series.  The
    # t0 placement is fully mixed BY CONSTRUCTION, so a handful of
    # first-slow-period waits are physics, not scheduling — the class
    # p99 only shows the separated regime once those are < 1% of the
    # fast-class samples (16 streams x 15 steps = 240 tolerates 2).
    num_frames = 16 if quick else 20
    steps_per_stream = num_frames - 1
    cfg = SLAMConfig(iters_track=3, iters_map=4, capacity=1024,
                     frag_capacity=48, map_window=2, scan_unroll=1,
                     keyframe=KeyframePolicy(kind="monogs", interval=3))
    names = registered_scenes()
    # Interleave classes so initial placement mixes fast and slow in the
    # same lockstep groups — the migrations have to EARN the separation.
    dss = {}
    for i in range(n_streams):
        sid = f"{'fast' if i % 2 == 0 else 'slow'}{i:02d}"
        dss[sid] = {"ds": make_dataset(names[i % len(names)],
                                       num_frames=num_frames, height=48,
                                       width=64, num_gaussians=400,
                                       frag_capacity=48, seed=i),
                    "cfg": cfg}

    tele = Telemetry.on(trace=trace)
    template = session_init(dss["fast00"]["ds"], cfg)
    ladder = PoolLadder(template, widths=widths, queue_depth=2,
                        telemetry=tele)
    baseline_caches = ladder.warmup()

    # Calibrate the widest rung's warm step time so the slow-class camera
    # period models a genuinely slower-than-compute stream on ANY host.
    widest = ladder.rungs[-1]
    blank = widest.server._blank
    sw = Stopwatch()
    for _ in range(3):
        widest.pool.step([blank] * widest.width)
    jax.block_until_ready(jax.tree.leaves(widest.pool.stacked))
    step_s = sw.elapsed() / 3
    widest.pool.stats = EngineStats()          # calibration is not serving
    widest.server.stats = ServeStats()

    slow_period = max(6.0 * step_s, 0.8)
    period_s = {sid: slow_period for sid in dss if sid.startswith("slow")}
    # starve_s ~ two warm steps: long enough that a merely compute-bound
    # lane is not misdiagnosed as blocked (admin swaps are device work
    # too — a trigger-happy policy melts into a migration storm whose
    # admin dispatches inflate every gap it is trying to close), short
    # enough that the t0 fully-mixed placement sorts itself well inside
    # the first slow period.
    policy = QueueDepthPolicy(starve_s=max(slow_period / 8, 2 * step_s),
                              cooldown_s=slow_period / 2,
                              max_migrations_per_tick=4)
    # Three floating slots: with one, a single eviction can strand the
    # ladder's only free slot inside the blocked lane itself (a group
    # cannot evict into its own slot), freezing the sort until some
    # stream happens to complete.  Three keep an eviction destination
    # AND a rescue destination in play at once.
    sched = SlamScheduler(ladder, policy=policy, telemetry=tele,
                          reserve_slots=3)
    for sid, d in dss.items():
        sched.admit(sid, session_init(d["ds"], cfg))
    worker = IngestWorker(sched, {sid: d["ds"].frames[1:]
                                  for sid, d in dss.items()},
                          period_s=period_s)
    sw = Stopwatch()
    worker.start()
    try:
        sched.serve(worker=worker, timeout_s=1800)
    finally:
        worker.stop()
    wall = sw.elapsed()
    assert worker.error is None
    assert sorted(sched.finished()) == sorted(dss), "streams went missing"
    caches_after = compile_cache_stats()

    reg = tele.registry
    per_group = {}
    for rung in ladder.rungs:
        disp = reg.sum_counters("dispatches", kind="step", group=rung.name)
        per_group[rung.name] = {
            "steps": rung.server.stats.steps,
            "registry_step_dispatches": disp,
            "pool_dispatches": rung.pool.stats.dispatches,
            "dispatches_per_frame_step": round(
                rung.pool.stats.dispatches
                / max(rung.server.stats.steps, 1), 3),
            "admits": rung.server.stats.admits,
            "retires": rung.server.stats.retires,
            "frames_dropped": rung.server.stats.frames_dropped,
        }
    migrations = reg.sum_counters("migrations")
    per_stream = {
        sid: {"frame_latency_ms":
              {k: round(v, 4) for k, v in latency_summary(
                  reg, "frame_latency_ms", stream=sid).items()},
              "queue_wait_ms":
              {k: round(v, 4) for k, v in latency_summary(
                  reg, "queue_wait_ms", stream=sid).items()}}
        for sid in dss}

    v1 = _v1_baseline(dss, period_s, widest.pool)
    v2_fast_p99 = _class_latency(reg, "queue_wait_ms", "fast")["p99_ms"]
    v1_fast_p99 = v1["queue_wait_ms"]["fast"]["p99_ms"]

    # Diagnostics before the gates, so a CI failure shows the shape of
    # the run and not just the failing comparison.
    print(f"serve_v2: wall {wall:.1f}s, {migrations} migration(s) "
          f"{sched.stats.migrations_by_reason}, per-group steps "
          f"{ {g: r['steps'] for g, r in per_group.items()} }",
          file=sys.stderr)
    for cls in ("fast", "slow"):
        print(f"serve_v2: {cls} queue wait v2="
              f"{_class_latency(reg, 'queue_wait_ms', cls)} v1="
              f"{v1['queue_wait_ms'][cls]}", file=sys.stderr)
    fast_p50s = [round(per_stream[sid]["queue_wait_ms"].get("p50_ms", 0.0))
                 for sid in sorted(dss) if sid.startswith("fast")]
    print(f"serve_v2: fast per-stream queue-wait p50s {fast_p50s}",
          file=sys.stderr)

    # ---- the PR's acceptance gates, asserted in-process -------------------
    assert caches_after == baseline_caches, (
        "recompile after warmup:\n"
        f"  warmup: {baseline_caches}\n  after:  {caches_after}")
    for gname, row in per_group.items():
        if row["steps"]:
            assert (row["registry_step_dispatches"] == row["steps"]
                    == row["pool_dispatches"]), (gname, row)
            assert row["dispatches_per_frame_step"] == 1.0, (gname, row)
        assert row["frames_dropped"] == 0, (gname, row)
    assert migrations >= 1, "mixed-rate run produced no migrations"
    assert v2_fast_p99 < v1_fast_p99, (
        f"fast-class p99 queue wait did not beat lockstep v1: "
        f"v2={v2_fast_p99}ms v1={v1_fast_p99}ms")

    trace_out = "bench_serve_trace_v2.json" if trace else ""
    if trace_out:
        tele.export_trace(trace_out)
    total_steps = sum(r["steps"] for r in per_group.values())
    summary = {
        "mode": "quick" if quick else "full",
        "scene_hw": [48, 64],
        "ladder_widths": list(widths),
        "streams": n_streams,
        "frames_per_stream": steps_per_stream,
        "slow_streams": len(period_s),
        "slow_period_s": round(slow_period, 3),
        "warm_step_s_widest": round(step_s, 4),
        "wall_s": round(wall, 3),
        "frames_per_s": round(n_streams * steps_per_stream
                              / max(wall, 1e-9), 3),
        "frame_steps": total_steps,
        "migrations": migrations,
        "migrations_by_reason": dict(sched.stats.migrations_by_reason),
        "admits": sched.stats.admits,
        "completions": sched.stats.completions,
        "admin_dispatches": reg.sum_counters("dispatches", kind="admin"),
        "recompiles_after_warmup": 0,
        "per_group": per_group,
        "frame_latency_ms": {
            "fast": _class_latency(reg, "frame_latency_ms", "fast"),
            "slow": _class_latency(reg, "frame_latency_ms", "slow")},
        "queue_wait_ms": {
            "fast": _class_latency(reg, "queue_wait_ms", "fast"),
            "slow": _class_latency(reg, "queue_wait_ms", "slow")},
        "fast_p99_queue_wait_ms": {"v2": v2_fast_p99, "v1": v1_fast_p99,
                                   "v1_over_v2": round(
                                       v1_fast_p99 / max(v2_fast_p99, 1e-9),
                                       2)},
        "per_stream": per_stream,
        "v1_baseline": v1,
    }
    if trace_out:
        summary["trace"] = trace_out
    emit("serve_v2/mixed32",
         1e6 / max(summary["frames_per_s"], 1e-9),
         f"migrations={migrations};fast_p99_v2={v2_fast_p99};"
         f"fast_p99_v1={v1_fast_p99};recompiles=0")

    report = {}
    if os.path.exists(out):
        with open(out) as fh:
            report = json.load(fh)
    report["serve_v2"] = stamp(summary, quick=quick)
    with open(out, "w") as fh:
        json.dump(report, fh, indent=2)
    return summary


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_slam.json")
    ap.add_argument("--worker", action="store_true",
                    help="(internal) run one device-count measurement in "
                         "this process; on the CPU requires XLA_FLAGS set "
                         "by the parent")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--frames", type=int, default=4)
    ap.add_argument("--trace-out", default="",
                    help="write the worker's Perfetto-loadable Chrome trace "
                         "JSON here (parent passes bench_serve_trace_D{d}"
                         ".json per device count)")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip Perfetto trace export")
    ap.add_argument("--v2", action="store_true",
                    help="run the SlamServe v2 mixed-rate continuous-"
                         "batching scenario (pool-width ladder + scheduler "
                         "+ threaded ingest) instead of the v1 lockstep "
                         "sweep")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true")
    mode.add_argument("--quick", action="store_true",
                      help="quick mode (the default; spelled out for CI "
                           "smoke jobs)")
    args = ap.parse_args()
    if args.worker:
        print(_RESULT_TAG + json.dumps(_measure(
            args.devices, args.sessions, args.frames,
            trace_out=args.trace_out)))
    elif args.v2:
        run_v2(quick=not args.full, out=args.out, trace=not args.no_trace)
    else:
        run(quick=not args.full, out=args.out, trace=not args.no_trace)
