#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  It makes the cell's streams on the device from
``--seed``, starts the system, runs the first keyframe period through the
window's own calls (set-up: this compiles, or loads from the compile cache,
every program the window uses), measures closed-loop frame-steps over whole
keyframe periods for about ``--seconds``, checks the first
``checked_frames`` frames of the run (warm-up and window) against the plain
reference once the window has closed, and prints one JSON object as the last
line of standard output.  ``--trace 1`` profiles the window and reports the per-layer metrics
instead of the end-to-end ones.

With no TPU, or fewer chips than the cell asks for, it exits with code 2
and prints no result.  ``--rehearse`` runs the same code on the CPU at tiny
sizes (Pallas in interpret mode) and prints no chip result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402

# JAX's persistent compilation cache, at one fixed path inside the checkout
# (the path is part of the cache's key): only a checkout's first run of a
# cell compiles.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(SystemExit):
    pass


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.2f} s] {msg}", file=sys.stderr,
          flush=True)


def rehearsal_sizes(cfg: dict, traffic: dict) -> tuple[dict, dict]:
    """Any cell cut to a CPU-sized stream: a 64x48 frame at the same field
    of view, a 1,024-Gaussian pool, 400 ground-truth Gaussians, fewer
    iterations, and the check cut to the first keyframe period: at 64x48
    two runs that differ by rounding drift apart within a few keyframes
    (a one-ulp change of the input moves the reference's own poses by up
    to 1e-2 by frame 16), so only that period separates a sound run from
    the lower-precision control.  Everything else, the keyframe interval
    included, stays."""
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    fr = cfg["frame"]
    f = 64.0 / fr["width"]
    cfg["frame"] = {"height": 48, "width": 64, "fx": fr["fx"] * f,
                    "fy": fr["fy"] * f, "cx": fr["cx"] * f, "cy": fr["cy"] * f}
    cfg["slam"].update(capacity=1024, iters_track=3, iters_map=4,
                       densify_per_kf=32)
    traffic["ground_truth"]["gaussians"] = 400
    traffic["trajectory"]["period_frames"] = 16
    traffic["checked_frames"] = traffic["warmup_frames"]
    return cfg, traffic


def start_jax(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if rehearse:
        if devs[0].platform != "cpu":
            raise SystemExit("--rehearse runs on the CPU only "
                             "(set JAX_PLATFORMS=cpu)")
    elif devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s), found {len(devs)} "
              f"{devs[0].platform} device(s); no result", file=sys.stderr)
        raise NoChip(2)
    if rehearse:
        return devs[0]
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devs[0]


@dataclasses.dataclass
class RunData:
    """What a per-layer metric's reader gets."""

    trace: object
    frames: int
    steps: int
    work: dict         # every DeviceWork counter, summed over the window
    peaks: dict
    counters: dict     # the program's build counters: "setup", the totals
    #                    at the end of set-up; "window", what it added


def memory_peak(stats: dict) -> int:
    """Peak device memory: the allocator's peak in use plus the peak it
    reserved for the programs' temporaries, which the TPU runtime holds
    apart from the bytes in use (5.1 GB for the solo step, against 0.77 GB
    in use)."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def run(args, root: str = ROOT, hooks=None) -> dict:
    """One run of one cell; returns the result object.  ``hooks`` (tests
    only) may replace the system's step underneath the harness."""
    import jax
    # Imported before anything compiles: its listener counts every build.
    from repro.obs.profiling import build_counters

    from bench import compare, harness, tracing, traffic

    cell = spec.load_cell(args.workload, root)
    cfg, mix = cell.config, cell.traffic
    if args.rehearse:
        cfg, mix = rehearsal_sizes(cfg, mix)
    dev = start_jax(cell.chips, args.rehearse)
    peaks = None if args.rehearse else spec.peaks_for(dev.device_kind)
    warm_frames, checked = mix["warmup_frames"], mix["checked_frames"]

    log(f"{cell.name} on {dev.device_kind}: seed {args.seed}")
    streams = [traffic.make_stream(s, args.seed, cfg, mix) for s in mix["streams"]]
    jax.block_until_ready([s.rgb for s in streams])
    log("streams made")
    system = harness.system(streams, cfg, mix["session_seed"],
                                  mix["max_frames"])
    if hooks:
        hooks(system)
    log("system started")
    warm = harness.warm_up(system, warm_frames)
    setup_s = time.perf_counter() - T0
    setup_builds = build_counters()
    log(f"warm-up period done: set-up {setup_s:.2f} s, "
        f"{setup_builds['jit_s']:.2f} s of it building "
        f"{setup_builds['programs']} program(s)")

    log_dir = None
    if args.trace:
        log_dir = args.keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(harness.SPAN_WINDOW):
            w = harness.measure(system, args.seconds, 4 * warm_frames,
                                args.trace, keep=checked - warm_frames)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    after = build_counters()
    builds = {"setup": setup_builds,
              "window": {k: after[k] - v for k, v in setup_builds.items()}}
    log(f"window built {builds['window']['programs']} program(s)")
    steps = harness.complete(system, warm + w.records, checked)
    peak_bytes = memory_peak(dev.memory_stats() or {})
    system.close()
    del system
    gc.collect()

    log(f"window: {w.frames} frames in {w.periods} periods, {w.seconds:.2f} s; "
        f"peak device memory {peak_bytes} bytes")
    ref = harness.run_reference(streams, cfg, checked, mix["session_seed"])
    log("reference done")
    limits = cfg["correct"]["limits"]
    nums = compare.numbers(steps, ref)
    correct, lines = compare.judge(nums, limits)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": w.frames, "failed": w.failed}
    if args.trace:
        tr = tracing.load_xplane(log_dir)
        w0, w1 = tr.window()
        device.update(busy_s=tracing.busy_ns(tr) * 1e-9, window_s=(w1 - w0) * 1e-9)
        data = RunData(trace=tr, frames=w.frames, steps=w.steps, work=w.work,
                       peaks=peaks, counters=builds)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(m["name"])(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        bd = tracing.breakdown(tr)
        if bd:
            result["breakdown"] = bd
        if not args.keep_trace:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        values = {"frames_per_s": w.frames / w.seconds,
                  "frame_ms_p95": percentile(w.latencies_ms, 95),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["window"] = {"seconds": w.seconds, "periods": w.periods,
                        "frames": w.frames, "steps": w.steps,
                        "work": dict(w.work)}
    result["builds"] = builds
    result["compared"] = compare.shown(nums, limits)
    for line in lines:
        print(f"compared: {line}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at tiny sizes; prints no chip result")
    ap.add_argument("--keep-trace", default=None,
                    help="write the profile here and keep it")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoChip as e:
        return int(e.code)
    line = json.dumps(result)
    if args.rehearse:
        print(f"rehearsal on the CPU at tiny sizes, not a chip result: {line}")
    else:
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
