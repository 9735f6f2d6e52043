#!/usr/bin/env python3
"""Device time of the SLAM step by the program's named scopes.

The program names its device work with ``jax.named_scope``
(``src/repro/obs/profiling.py``): the step's phases (``PHASES``) and the
work scopes of the functions that do the work (``WORK``).  A TPU profile
keeps each device operation's scope path as the ``tf_op`` stat of the
operation's metadata in the ``.xplane.pb``, and its source line as
``source``; ``bench/tracing.py`` loads them with each operation.

Each operation goes to its innermost work scope.  Where XLA dropped the
path (an operation that layout assignment or a loop's carry made, or one
that kept only its primitive's name, such as the ``reduce_window_sum`` of
a ``cumsum``), the operation's source file names the scope, and failing
that the loop or conditional that holds it (:func:`attribute`).  The
Pallas kernels (the ``KERNELS`` that ``bench/metrics`` reads) and the
operations that hold others are left out of the work scopes; the phases
count the kernels.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> \
        --out <dir>

runs one ``--trace 1`` run of the cell through ``bench/run.py`` in this
process, keeps its profile under ``<dir>``, and prints one JSON object:
the harness's result line, the split of the window's device time in
ms/frame, the ``slam.step`` host spans, and the program's build counters
at the end of set-up and over the window.

    python3 bench/scopes.py --trace-dir <dir> [--frames <n>]

reduces a kept profile alone.  The per-layer metrics of the work buckets
and the phases (``bench/metrics/``) read the same reduction through
:func:`split`.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, tracing  # noqa: E402

PHASES = ("slam.track", "slam.map", "slam.keyframe", "slam.paged")
WORK = ("project", "frag_build", "wsu_schedule", "raster", "loss", "adam",
        "prune", "densify")
# The reduction's buckets, by the work scopes each sums.
BUCKETS = {"project": ("project",), "frag_build": ("frag_build",),
           "wsu_schedule": ("wsu_schedule",),
           "raster_glue": ("raster", "loss"),
           "optim": ("adam", "prune", "densify")}
KERNELS = ("tile_render_fwd", "tile_render_bwd", "block_cumsum")
# Source files whose operations belong to one work scope, for operations
# whose scope path XLA dropped.
SOURCES = {"core/projection.py": "project", "core/sorting.py": "frag_build",
           "core/schedule.py": "wsu_schedule", "core/render.py": "raster",
           "kernels/ops.py": "raster", "kernels/gmu.py": "raster",
           "kernels/ref.py": "raster", "core/losses.py": "loss",
           "train/optimizer.py": "adam", "core/pruning.py": "prune"}
SPAN_STEP = "slam.step"


# -- scope paths ------------------------------------------------------------

# The reader of the scope metadata and the event that carries it live in
# ``bench/tracing.py``; these names are kept for the reduction's callers.
op_metadata = tracing.op_metadata
ScopedEvent = tracing.Event


def components(path: str) -> list:
    """The scope names of an ``op_name`` path, transforms unwrapped:
    ``jit(solo)/transpose(jvp(slam.map))/while/body/raster/mul:`` ->
    ``[solo, slam.map, while, body, raster, mul]``.  Where XLA merged
    instructions the path holds several, joined by ``;``: the first is read."""
    first = path.split(";", 1)[0].rstrip(":")
    out = []
    for part in first.split("/"):
        m = re.fullmatch(r"(?:[\w.-]+\()*([^()]*)\)*", part)
        out.append(m.group(1) if m else part)
    return out


def work_of(path: str) -> str | None:
    """The innermost work scope on the path."""
    return next((c for c in reversed(components(path)) if c in WORK), None)


def phase_of(path: str) -> str | None:
    return next((c for c in components(path) if c in PHASES), None)


def work_of_source(source: str) -> str | None:
    path = source.rsplit(":", 1)[0].replace(os.sep, "/")
    return next((w for f, w in SOURCES.items() if path.endswith(f)), None)


def attribute(ops) -> list:
    """``[(event, work scope or None, phase or None)]`` for the operations
    of one chip.

    The work scope is the innermost on the operation's own path, else its
    source file's, else that of the innermost loop or conditional that
    holds it on the timeline.  The phase is that of the outermost holder
    that has one, else the path's own.  XLA drops the path of the step's
    own loops and conditionals, so a holder's phase is the one its
    operations' paths give most time to; and it shares one computation
    between identical call sites (the fragment build's search in tracking
    and in mapping), whose operations then carry one site's path only."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    holders, stack = [], []      # per operation: its holders, outermost first
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        holders.append(tuple(stack))
        if tracing.is_container(e.name):
            stack.append(i)
    votes: dict = {}
    for e, hs in zip(ops, holders):
        p = phase_of(e.scope)
        if p and not tracing.is_container(e.name):
            for h in hs:
                votes.setdefault(h, {}).setdefault(p, 0.0)
                votes[h][p] += e.dur_ns

    def own_phase(i):
        p = phase_of(ops[i].scope)
        if p or i not in votes:
            return p
        return max(votes[i].items(), key=lambda kv: kv[1])[0]

    out, work_at = [], {}
    for i, (e, hs) in enumerate(zip(ops, holders)):
        work = (work_of(e.scope) or work_of_source(e.source)
                or (work_at[hs[-1]] if hs else None))
        phase = next((p for p in map(own_phase, (*hs, i)) if p), None)
        if tracing.is_container(e.name):
            work_at[i] = work
        out.append((e, work, phase))
    return out


# -- the reduction ----------------------------------------------------------

def device_split(trace: tracing.Trace, frames: int) -> dict | None:
    """The window's device time in ms/frame, averaged over the chips:
    ``work`` by bucket (``BUCKETS``, then ``step_unscoped``: the device
    time outside the kernels that no bucket holds, so that the buckets,
    ``kernels`` and ``step_unscoped`` add up to ``busy``), ``phases`` (all
    operations under each phase, kernels included; ``none`` outside every
    phase), ``phase_work`` (phase x work scope) and ``unscoped_top`` (the
    operations outside the kernels with no work scope, largest first)."""
    if not trace.devices or not any(trace.devices):
        return None
    w0, w1 = trace.window()
    chips = len(trace.devices)
    per = 1e-6 / frames / chips
    work = dict.fromkeys(BUCKETS, 0.0)
    phases = dict.fromkeys((*PHASES, "none"), 0.0)
    phase_work: dict = {}
    lost: dict = {}
    bucket_of = {w: b for b, ws in BUCKETS.items() for w in ws}
    for ops in trace.devices:
        for e, w, p in attribute(ops):
            if tracing.is_container(e.name):
                continue
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t <= s:
                continue
            ns = (t - s) * per
            phases[p or "none"] += ns
            kernel = tracing.matches(e, KERNELS)
            key = f"{p or 'none'}/{'kernels' if kernel else w or 'none'}"
            phase_work[key] = phase_work.get(key, 0.0) + ns
            if kernel:
                continue
            if w in bucket_of:
                work[bucket_of[w]] += ns
            else:
                k = (e.name, e.scope)
                lost[k] = lost.get(k, 0.0) + ns
    busy = tracing.busy_ns(trace) * 1e-6 / frames
    kernels = tracing.kernel_ns(trace, KERNELS) * 1e-6 / frames
    step_xla = max(busy - kernels, 0.0)
    work["step_unscoped"] = max(step_xla - sum(work.values()), 0.0)
    top = sorted(lost.items(), key=lambda kv: -kv[1])[:10]
    return {"busy": busy, "kernels": kernels, "step_xla": step_xla,
            "work": work, "phases": phases,
            "phase_work": dict(sorted(phase_work.items())),
            "unscoped_top": [[n, s, ms] for (n, s), ms in top]}


def host_split(trace: tracing.Trace) -> dict:
    """The program's ``slam.step`` host spans in the window, per step (one
    ``bench.dispatch`` span is one step), and how many of them lie outside
    every ``bench.dispatch`` span (expected 0)."""
    w0, w1 = trace.window()
    inside = [e for e in trace.host if w0 <= e.start_ns and e.end_ns <= w1]
    dispatch = [e for e in inside if e.name == harness.SPAN_DISPATCH]
    steps = [e for e in inside if e.name == SPAN_STEP]
    outside = sum(not any(d.start_ns <= e.start_ns and e.end_ns <= d.end_ns
                          for d in dispatch) for e in steps)
    n = len(dispatch)
    return {"steps": n, "slam_steps": len(steps),
            "slam_step_ms_per_step": (sum(e.dur_ns for e in steps) * 1e-6 / n
                                      if n else None),
            "dispatch_ms_per_step": (sum(e.dur_ns for e in dispatch) * 1e-6 / n
                                     if n else None),
            "slam_steps_outside_dispatch": outside}


def reduce(trace: tracing.Trace, frames: int | None = None) -> dict:
    host = host_split(trace)
    return {"device": device_split(trace, frames or host["steps"]),
            "host": host}


@functools.lru_cache(maxsize=1)
def _split(trace: tracing.Trace, frames: int) -> dict | None:
    if not any(e.scope for ops in trace.devices for e in ops):
        return None
    return device_split(trace, frames)


def split(run) -> dict | None:
    """:func:`device_split` of a per-layer reader's run, reduced once for
    all the readers of one trace; None where no device operation carries a
    scope path, as in a profile without the program's metadata: nothing is
    there to attribute."""
    return _split(run.trace, run.frames)


# -- one traced run with the build counters ---------------------------------

def traced_run(workload: str, seed: int, seconds: float, out: str,
               rehearse: bool = False) -> dict:
    """One ``--trace 1`` run of ``workload`` (its profile kept under
    ``out``), reduced, with the program's build counters as the harness
    takes them: ``setup`` at the end of set-up, ``window`` over the window."""
    from bench import run as bench_run

    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=1, rehearse=rehearse, keep_trace=out)
    result = bench_run.run(args)
    return {"result": result,
            "split": reduce(tracing.load_xplane(out),
                            result["window"]["frames"]),
            "builds": result["builds"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", help="reduce this kept profile alone")
    ap.add_argument("--frames", type=int, default=None)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", help="keep the run's profile here")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.trace_dir:
        out = reduce(tracing.load_xplane(args.trace_dir), args.frames)
    else:
        if not (args.workload and args.seed is not None and args.seconds
                and args.out):
            ap.error("give --trace-dir, or --workload, --seed, --seconds "
                     "and --out")
        from bench import run as bench_run
        try:
            out = traced_run(args.workload, args.seed, args.seconds,
                             args.out, args.rehearse)
        except bench_run.NoChip as e:
            return int(e.code)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
