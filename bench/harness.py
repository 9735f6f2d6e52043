"""Driving one cell: set-up, the measured window, the check, the result.

The system under test is the program's own entry points, driven by the
module that the configuration's ``path`` names (``bench/systems/``):
``session_init`` and ``session_step`` for a solo stream.  Everything else
here belongs to the benchmark.

Load is closed-loop: each stream has one frame outstanding, and the next
frame goes in as soon as the last pose is back on the host.  The window
covers whole keyframe periods: it starts right after a keyframe and ends at
the last keyframe reached within ``--seconds``, so every window has the
same mix of tracking-only frames and keyframes.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import time

import jax
import numpy as np

from bench import compare, spec, traffic

SPAN_DISPATCH = "bench.dispatch"   # host time inside the calls into the program
SPAN_FETCH = "bench.fetch"         # waiting for the poses to reach the host
SPAN_WINDOW = "bench.window"


# Fields of the configuration's "slam" object that are objects of their own
# in the program, by the type that holds them.
NESTED = {"prune": ("repro.core.pruning", "PruneConfig"),
          "keyframe": ("repro.core.keyframes", "KeyframePolicy"),
          "downsample": ("repro.core.downsample", "DownsampleConfig"),
          "paged": ("repro.slam.map.paged", "PagedConfig")}


def slam_config(cfg: dict):
    """The program's ``SLAMConfig`` from the configuration file: every key
    of its ``slam`` object is a field of that name."""
    from repro.slam.session import SLAMConfig

    fields = {}
    for key, value in cfg["slam"].items():
        if key in NESTED and value is not None:
            module, name = NESTED[key]
            value = getattr(importlib.import_module(module), name)(**value)
        fields[key] = value
    return SLAMConfig(**fields)


def first_frame(stream: traffic.Stream, cfg: dict):
    """Frame 0 with its ground-truth pose, as the program's data type."""
    from repro.core.camera import Intrinsics
    from repro.slam.datasets import Frame, SLAMDataset

    fr = cfg["frame"]
    intr = Intrinsics(fx=fr["fx"], fy=fr["fy"], cx=fr["cx"], cy=fr["cy"],
                      width=fr["width"], height=fr["height"])
    rgb, depth = stream.frame(0)
    f0 = Frame(rgb=np.asarray(rgb), depth=np.asarray(depth),
               w2c_gt=stream.poses[0])
    return SLAMDataset(name=stream.scene, intrinsics=intr, frames=[f0],
                       gt_field=None)


def record(res, row=None) -> dict:
    """One stream's step result on the host; ``work`` holds every counter
    of the step's ``DeviceWork``, by its field name."""
    pick = (lambda x: x) if row is None else (lambda x: x[row])
    return {"pose": np.asarray(pick(res["pose"]), np.float64),
            "is_kf": bool(pick(res["is_kf"])),
            "track_losses": np.asarray(pick(res["track_losses"]), np.float64),
            "map_losses": np.asarray(pick(res["map_losses"]), np.float64),
            "psnr": float(pick(res["psnr"])),
            "work": {k: int(pick(v)) for k, v in res["work"].items()}}


def fetch(out):
    return jax.device_get({"pose": out.pose, "is_kf": out.is_kf,
                           "track_losses": out.track_losses,
                           "map_losses": out.map_losses, "psnr": out.psnr,
                           "work": out.work._asdict()})


def system(streams, cfg: dict, session_seed: int, max_frames: int):
    """The system module that the configuration's ``path`` names
    (``bench/systems/<path>.py``), started on ``streams``."""
    mod = spec.load_module("systems", cfg["path"])
    return mod.System(streams, cfg, session_seed, max_frames)




@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    frames: int = 0
    steps: int = 0
    periods: int = 0
    latencies_ms: list = dataclasses.field(default_factory=list)
    failed: int = 0
    # Every DeviceWork counter summed over the window's frames (a counter
    # the window never saw reads 0).
    work: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    records: list = dataclasses.field(default_factory=list)


def step_once(system, annotate: bool):
    """Submit, dispatch, and wait for every stream's pose: one closed-loop
    frame-step.  Returns (records, seconds from submit to poses on host)."""
    t0 = time.perf_counter()
    if annotate:
        with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
            system.dispatch()
        with jax.profiler.TraceAnnotation(SPAN_FETCH):
            recs = system.fetch()
    else:
        system.dispatch()
        recs = system.fetch()
    return recs, time.perf_counter() - t0


def measure(system, seconds: float, period_max: int, annotate: bool,
            keep: int = 0) -> Window:
    """Closed-loop frame-steps over whole keyframe periods until the next
    period would end past ``seconds``; a period still open after
    ``period_max`` frames ends the window.  The records of the first
    ``keep`` frame-steps are kept for the check."""
    w = Window()
    pending_lat, pending_failed, since_kf = [], 0, 0
    pending_work = collections.Counter()
    t0 = time.perf_counter()
    while True:
        recs, lat = step_once(system, annotate)
        if len(w.records) < keep:
            w.records.append(recs)
        since_kf += 1
        pending_lat += [lat * 1e3] * len(recs)
        pending_failed += sum(not np.isfinite(r["pose"]).all() for r in recs)
        for r in recs:
            pending_work.update(r["work"])
        kf = [r["is_kf"] for r in recs]
        if since_kf > period_max or any(kf) != all(kf):
            # No period closes (a stream keyframes off its interval): the
            # window ends here and its open frames count as failed.
            w.seconds = time.perf_counter() - t0
            w.frames += len(pending_lat)
            w.failed += len(pending_lat)
            w.latencies_ms += pending_lat
            w.steps += since_kf
            return w
        if not kf[0]:
            continue
        elapsed = time.perf_counter() - t0
        w.periods += 1
        w.seconds = elapsed
        w.latencies_ms += pending_lat
        w.steps += since_kf
        w.frames += len(pending_lat)
        w.failed += pending_failed
        w.work.update(pending_work)
        pending_work.clear()
        pending_lat, pending_failed, since_kf = [], 0, 0
        if elapsed + elapsed / w.periods > seconds:
            return w


def warm_up(system, frames: int) -> list:
    """The first keyframe period through the window's own calls and feed:
    compiles every program the window uses and returns each step's records
    (the first of those the check compares)."""
    return [step_once(system, annotate=False)[0] for _ in range(frames)]


def complete(system, records: list, frames: int) -> list:
    """``records`` (warm-up and window, in order) carried on to ``frames``
    frame-steps through the same calls, after the window has closed and
    outside its time: the check compares as many frames wherever the window
    happens to end."""
    while len(records) < frames:
        records.append(step_once(system, annotate=False)[0])
    return records[:frames]


def run_reference(streams, cfg: dict, steps: int, session_seed: int,
                  precision: str = "highest") -> list:
    """Per stream, the reference's records of frames 1..steps."""
    ref = compare.reference_stream(cfg, precision)
    out = []
    for s in streams:
        frames = [(np.asarray(s.frame(i)[0]), np.asarray(s.frame(i)[1]),
                   s.poses[i % len(s.poses)]) for i in range(steps + 1)]
        out.append(ref.run(frames, steps, session_seed))
    return out
