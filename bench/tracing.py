"""Reduction of a profiler trace to busy time, kernel time and idle gaps.

The profiler writes an ``.xplane.pb``; :func:`load_xplane` turns it into a
:class:`Trace`: the device's operations (one list per chip) and the
benchmark's own host spans, on one clock.  Everything else works on that
plain form, so it can be checked on small made-up traces.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from bench.harness import SPAN_DISPATCH, SPAN_FETCH, SPAN_WINDOW


@dataclasses.dataclass(frozen=True)
class Event:
    start_ns: float
    dur_ns: float
    name: str               # the HLO instruction's name, e.g. "fusion.12"

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: list           # per chip, a list of device-operation Events
    host: list              # the benchmark's own host spans (Events)

    def window(self) -> tuple:
        spans = [e for e in self.host if e.name == SPAN_WINDOW]
        if not spans:
            raise ValueError(f"no {SPAN_WINDOW!r} span in the trace")
        return spans[0].start_ns, spans[0].end_ns


# The line of a TPU device plane that holds one event per executed
# operation.  Its events are named by the whole HLO instruction
# ("%fusion.12 = f32[...] fusion(...), ..."); operations that hold others
# (a while loop, a conditional, a call) span their bodies' operations.
OP_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """The instruction's own name, without its operands (which name the
    operations it reads from)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def is_container(name: str) -> bool:
    return name.split(".", 1)[0] in CONTAINERS


def load_xplane(log_dir: str) -> Trace:
    """The device operations of every ``/device:TPU:<n>`` plane and the
    ``bench.*`` host spans from the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [Event(e.start_ns, e.duration_ns, op_name(e.name))
                            for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.start_ns, e.duration_ns, e.name)
                         for e in line.events if e.name.startswith("bench.")]
    return Trace(devices=devices, host=host)


def _clip(events, w0, w1):
    for e in events:
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t > s:
            yield s, t, e


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_ns(trace: Trace) -> float:
    """Device-busy time in the window, averaged over the chips."""
    w0, w1 = trace.window()
    if not trace.devices:
        return 0.0
    per = [sum(t - s for s, t in union((s, t) for s, t, _ in _clip(ops, w0, w1)))
           for ops in trace.devices]
    return sum(per) / len(per)


def idle_share(trace: Trace) -> float | None:
    """1 - busy / window, in percent; None where no device was traced."""
    if not trace.devices or not any(trace.devices):
        return None
    w0, w1 = trace.window()
    return 100.0 * (1.0 - busy_ns(trace) / (w1 - w0))


def matches(event: Event, names) -> bool:
    return any(n in event.name for n in names)


def kernel_ns(trace: Trace, names) -> float:
    """Summed device time of the operations whose name contains one of
    ``names``, in the window, averaged over the chips."""
    w0, w1 = trace.window()
    if not trace.devices:
        return 0.0
    per = [sum(t - s for s, t, e in _clip(ops, w0, w1) if matches(e, names))
           for ops in trace.devices]
    return sum(per) / len(per)


def host_ns(trace: Trace, span: str) -> float:
    w0, w1 = trace.window()
    return sum(t - s for s, t, e in _clip(trace.host, w0, w1) if e.name == span)


def gaps(trace: Trace, chip: int = 0) -> list:
    """Idle intervals of one chip in the window, each labelled with the
    benchmark's host span that covers its midpoint (``host:other`` when
    none does).  Returns [(label, seconds, start_ns)], longest first."""
    w0, w1 = trace.window()
    busy = union((s, t) for s, t, _ in _clip(trace.devices[chip], w0, w1))
    holes, cur = [], w0
    for s, t in busy:
        if s > cur:
            holes.append((cur, s))
        cur = max(cur, t)
    if cur < w1:
        holes.append((cur, w1))
    spans = [e for e in trace.host if e.name in (SPAN_DISPATCH, SPAN_FETCH)]
    out = []
    for s, t in holes:
        mid = 0.5 * (s + t)
        label = next((e.name for e in spans if e.start_ns <= mid < e.end_ns),
                     "host:other")
        out.append((label, (t - s) * 1e-9, s))
    return sorted(out, key=lambda g: -g[1])


def top_ops(trace: Trace, n: int = 10, chip: int = 0) -> list:
    """The ``n`` device operations (by name, loops and conditionals left
    out: they hold others) that took most time in the window:
    [(name, seconds)]."""
    w0, w1 = trace.window()
    tot: dict = {}
    for s, t, e in _clip(trace.devices[chip], w0, w1):
        if not is_container(e.name):
            tot[e.name] = tot.get(e.name, 0.0) + (t - s)
    return [(k, v * 1e-9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace) -> dict | None:
    if not trace.devices or not any(trace.devices):
        return None
    return {"device_ops": [list(x) for x in top_ops(trace)],
            "idle_gaps": [[label, sec] for label, sec, _ in gaps(trace)[:10]]}
