"""SlamServe v2 demo: continuous batching over the pool-width ladder.

v1 (examples/serve_slam.py) serves one fixed-width lockstep pool: every
live stream must have a frame queued before ANY of them can dispatch, so
one slow camera stalls its whole batch, and "one more stream than the
pool holds" means a multi-second recompile.  This demo runs the sched
tier instead:

* a :class:`PoolLadder` pre-compiles serving pools at a ladder of widths
  (default S ∈ {1, 2}) sharing one compile cache — admission after
  :meth:`warmup` NEVER compiles;
* an :class:`IngestWorker` producer thread decodes and stages frames off
  the dispatch thread, pacing one stream like a slow camera;
* the :class:`SlamScheduler` dispatches each group independently and,
  when the slow stream starves its lockstep peers, migrates rows between
  pools (cached slot-swap executables, counted as admin dispatches) —
  per-stream trajectories stay bitwise-equal to solo runs throughout
  (tests/test_sched.py proves it).

More streams than slots is fine: the scheduler queues admissions and
recycles slots as streams finish.

Run:  PYTHONPATH=src python examples/sched_serve.py [--frames 6]
          [--streams 4] [--widths 1,2] [--slow-period 0.5]
          [--trace out.json]
"""

import argparse

from repro.core.keyframes import KeyframePolicy
from repro.obs import Stopwatch, Telemetry, latency_summary
from repro.slam.datasets import make_dataset, registered_scenes
from repro.slam.sched import IngestWorker, PoolLadder, QueueDepthPolicy, \
    SlamScheduler
from repro.slam.server import compile_cache_stats
from repro.slam.session import SLAMConfig, session_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=6)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--widths", default="1,2",
                    help="comma-separated ladder pool widths (compile cost "
                         "scales with each width; the BENCH row uses 2,4,8)")
    ap.add_argument("--slow-period", type=float, default=0.5,
                    help="seconds between frames of the slow 'camera' "
                         "stream (stream 0)")
    ap.add_argument("--trace", default="", metavar="out.json",
                    help="export a SlamScope Chrome-trace JSON of the run "
                         "(open in Perfetto: ui.perfetto.dev)")
    args = ap.parse_args()
    widths = tuple(int(w) for w in args.widths.split(","))
    tele = Telemetry.on(trace=bool(args.trace))

    cfg = SLAMConfig(
        iters_track=4, iters_map=6, capacity=2048, frag_capacity=64,
        map_window=2, scan_unroll=1,
        keyframe=KeyframePolicy(kind="monogs", interval=3),
    )
    names = registered_scenes()
    print(f"generating {args.streams} synthetic streams ({args.frames} "
          "frames each)…")
    streams = {}
    for i in range(args.streams):
        sid = ("slow0" if i == 0 else f"fast{i}")
        streams[sid] = make_dataset(names[i % len(names)],
                                    num_frames=args.frames, height=64,
                                    width=64, num_gaussians=1000,
                                    frag_capacity=64, seed=i)

    template = session_init(next(iter(streams.values())), cfg)
    ladder = PoolLadder(template, widths=widths, telemetry=tele)
    print(f"warming ladder S={list(ladder.widths)} "
          f"({ladder.capacity} slots)… (one-time compile)")
    sw = Stopwatch()
    baseline = ladder.warmup()
    print(f"  warm in {sw.elapsed():.1f}s; admission is now a cached "
          "slot-swap")

    policy = QueueDepthPolicy(starve_s=args.slow_period / 4,
                              cooldown_s=args.slow_period)
    sched = SlamScheduler(ladder, policy=policy, telemetry=tele,
                          reserve_slots=1)
    for sid, ds in streams.items():
        sched.admit(sid, session_init(ds, cfg))
    worker = IngestWorker(sched, {sid: ds.frames[1:]
                                  for sid, ds in streams.items()},
                          period_s={"slow0": args.slow_period})

    sw = Stopwatch()
    worker.start()
    try:
        sched.serve(worker=worker)
    finally:
        worker.stop()
    wall = sw.elapsed()

    reg = tele.registry
    steps = sum(r.server.stats.steps for r in ladder.rungs)
    print(f"\nserved {len(streams)} streams x {args.frames - 1} "
          f"frame-steps in {wall:.1f}s ({steps} group dispatches, "
          f"{sched.stats.migrations} migration(s), "
          f"{reg.sum_counters('dispatches', kind='admin')} admin "
          "dispatches)")
    for rung in ladder.rungs:
        disp = reg.sum_counters("dispatches", kind="step", group=rung.name)
        print(f"  {rung.name}: {rung.server.stats.steps} steps, "
              f"{disp / max(rung.server.stats.steps, 1):.2f} "
              "dispatches/frame-step")
    print("zero recompiles after warmup:",
          compile_cache_stats() == baseline)
    for sid in sorted(streams):
        lat = latency_summary(reg, "queue_wait_ms", stream=sid)
        if lat.get("count"):
            print(f"  {sid}: queue wait p50 {lat['p50_ms']:.1f} ms | "
                  f"p99 {lat['p99_ms']:.1f} ms")
    if tele.export_trace(args.trace):
        print(f"trace: wrote {args.trace} (load at ui.perfetto.dev)")

    print(f"\n{'stream':>8} {'scene':>8} {'ATE cm':>8} {'PSNR dB':>8} "
          f"{'keyframes':>9}")
    for sid, ds in sorted(streams.items()):
        fin = sched.result(sid, gt_w2c=[f.w2c_gt for f in ds.frames])
        print(f"{sid:>8} {ds.name:>8} {fin.ate * 100:>8.2f} "
              f"{fin.mean_psnr:>8.2f} {len(fin.keyframe_psnr):>9}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
