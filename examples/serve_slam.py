"""SlamServe demo: concurrent RGB-D streams through the device-sharded,
queue-fed serving tier.

Each stream is a different synthetic scene (heterogeneous workloads —
including 'stairs0', the depth/occupancy-skewed one).  Frames are
``submit``-ted into per-stream bounded queues; the :class:`SlamServer`
dispatcher fires ONE asynchronous sharded dispatch per lockstep
frame-step (``ShardedPool`` lays session rows out on the mesh's "data"
axis — with one local device everything lands on it, on a multi-device
host rows spread D-ways), staging the next batch while the devices
compute.  Mid-run, one stream is retired and a fresh scene admitted into
its slot — per-row outputs stay bitwise-equal to solo runs throughout
(tests/test_serve.py proves it).

Run:  PYTHONPATH=src python examples/serve_slam.py [--frames 8]
          [--sessions 4] [--devices N] [--no-swap] [--trace out.json]
"""

import argparse

from repro.core.keyframes import KeyframePolicy
from repro.launch.mesh import make_data_mesh
from repro.obs import Stopwatch, Telemetry, latency_summary
from repro.slam.datasets import make_dataset, registered_scenes
from repro.slam.server import ShardedPool, SlamServer
from repro.slam.session import SLAMConfig, session_finalize, session_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--devices", type=int, default=None,
                    help="'data'-axis mesh size (default: all local "
                         "devices; sessions must divide evenly)")
    ap.add_argument("--no-swap", action="store_true",
                    help="skip the mid-run retire/admit demonstration")
    ap.add_argument("--trace", default="", metavar="out.json",
                    help="export a SlamScope Chrome-trace JSON of the run "
                         "(open in Perfetto: ui.perfetto.dev)")
    args = ap.parse_args()
    s = args.sessions
    tele = Telemetry.on(trace=bool(args.trace))

    cfg = SLAMConfig(
        iters_track=4, iters_map=6, capacity=2048, frag_capacity=64,
        map_window=2, scan_unroll=1,
        keyframe=KeyframePolicy(kind="monogs", interval=3),
    )
    names = registered_scenes()
    print(f"generating {s + 1} synthetic streams ({args.frames} frames "
          "each)…")
    streams = [make_dataset(names[i % len(names)], num_frames=args.frames,
                            height=64, width=64, num_gaussians=1000,
                            frag_capacity=64, seed=i) for i in range(s + 1)]
    spare = streams.pop()       # admitted mid-run when a slot frees up

    mesh = make_data_mesh(args.devices)
    pool = ShardedPool([session_init(ds, cfg) for ds in streams], mesh=mesh)
    srv = SlamServer(pool, queue_depth=2, telemetry=tele)
    print(f"pool: {pool.size} session rows sharded over "
          f"{pool.num_devices} device(s) on the 'data' axis")

    swap_at = None if args.no_swap else max(args.frames // 2, 2)
    live = {slot: ds for slot, ds in enumerate(streams)}
    cursor = {slot: 1 for slot in live}         # next frame per stream
    retired = []

    sw = Stopwatch()
    for t in range(1, args.frames):
        if t == swap_at:
            # Admission control: stream 0 hands its slot to the spare.
            retired.append((streams[0], srv.retire(0)))
            slot = srv.admit(session_init(spare, cfg))
            live[slot] = spare
            cursor[slot] = 1
            print(f"  t={t}: retired slot 0 ({streams[0].name}), admitted "
                  f"{spare.name} (admission swap, "
                  f"{pool.admin_dispatches} admin dispatch)")
        for slot, ds in live.items():
            if cursor[slot] < ds.num_frames:
                srv.submit(slot, ds.frames[cursor[slot]])
                cursor[slot] += 1
        srv.pump()              # async: staging overlaps device compute
    srv.drain()                 # the one sync
    wall = sw.elapsed()

    steps = srv.stats.steps
    print(f"\nserved {s} slots x {steps} frame-steps in {wall:.1f}s "
          f"(incl. one-time compile)")
    print(f"dispatches: {pool.stats.dispatches} total = "
          f"{pool.stats.dispatches / max(steps, 1):.2f} per frame-step = "
          f"{pool.stats.dispatches / max(s * steps, 1):.2f} per "
          "stream-frame (solo serving would pay ~1.0)")
    print(f"syncs: {pool.stats.syncs}; queue wait "
          f"{srv.stats.queue_wait_ms_per_frame:.2f} ms/frame; host staging "
          f"{srv.stats.stage_s:.2f}s total; "
          f"{srv.stats.backpressure_events} backpressure event(s)")
    lat = latency_summary(tele.registry)
    if lat.get("count"):
        print(f"frame latency (submit→dispatch-return, pool-merged): "
              f"p50 {lat['p50_ms']:.2f} ms | p90 {lat['p90_ms']:.2f} ms | "
              f"p99 {lat['p99_ms']:.2f} ms | queue-depth hwm "
              f"{tele.registry.max_gauge_hwm('queue_depth')}")
    if tele.export_trace(args.trace):
        print(f"trace: wrote {args.trace} (load at ui.perfetto.dev)")

    print(f"\n{'slot':>4} {'scene':>8} {'ATE cm':>8} {'PSNR dB':>8} "
          f"{'keyframes':>9}")
    for slot, ds in sorted(live.items()):
        fin = pool.finalize(slot, gt_w2c=[f.w2c_gt for f in ds.frames])
        print(f"{slot:>4} {ds.name:>8} {fin.ate * 100:>8.2f} "
              f"{fin.mean_psnr:>8.2f} {len(fin.keyframe_psnr):>9}")
    for ds, sess in retired:
        fin = session_finalize(sess, gt_w2c=[f.w2c_gt for f in ds.frames])
        print(f"{'ret':>4} {ds.name:>8} {fin.ate * 100:>8.2f} "
              f"{fin.mean_psnr:>8.2f} {len(fin.keyframe_psnr):>9}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
