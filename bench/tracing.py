"""Reduction of a profiler trace to busy time, kernel time and idle gaps.

The profiler writes an ``.xplane.pb``; :func:`load_xplane` turns it into a
:class:`Trace`: the device's operations (one list per chip), each with the
scope path and source line that the program's metadata gives it, and the
benchmark's and the program's host spans, on one clock.  Everything else
works on that plain form, so it can be checked on small made-up traces.
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
    scope: str = ""         # its op_name (``tf_op``) path
    source: str = ""        # "<file>:<line>" of the user code that made it

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(eq=False)          # compared and hashed by identity
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


# -- the .xplane.pb's metadata ----------------------------------------------
#
# ``jax.profiler.ProfileData`` does not expose the stats of an event's
# metadata, where a TPU profile keeps each operation's scope path (``tf_op``)
# and source line (``source``), so :func:`op_metadata` reads them from the
# file's protobuf itself.

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for varint and
    fixed-width fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, val


def _map_entry(buf):
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def op_metadata(path: str) -> dict:
    """``{device plane: {operation's full name: (tf_op, source)}}`` from the
    ``XEventMetadata`` of each ``/device:TPU:<n>`` plane of an XSpace.

    XSpace.planes = 1; XPlane: name 2, event_metadata 4, stat_metadata 5
    (maps: key 1, value 2); XEventMetadata: name 2, stats 5;
    XStatMetadata: name 2; XStat: metadata_id 1, str_value 5, ref_value 7
    (the id of a stat metadata whose name is the value)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_map_entry(v)[1])
            elif f == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next((bytes(x).decode() for g, x in
                                        _fields(meta) if g == 2), "")
        if not is_device_plane(name):
            continue
        ops = {}
        for ev in events:
            op, stats = "", {}
            for f, v in _fields(ev):
                if f == 2:
                    op = bytes(v).decode()
                elif f == 5:
                    sid, val = None, ""
                    for g, x in _fields(v):
                        if g == 1:
                            sid = x
                        elif g == 5:
                            val = bytes(x).decode()
                        elif g == 7:
                            val = stat_names.get(x, "")
                    stats[stat_names.get(sid)] = val
            ops[op] = (stats.get("tf_op", ""), stats.get("source", ""))
        out[name] = ops
    return out


def load_xplane(log_dir: str) -> Trace:
    """The device operations of every ``/device:TPU:<n>`` plane, each with
    its scope path and source (:func:`op_metadata`), and the host spans of
    the benchmark (``bench.*``) and of the program (``slam.*``) from the
    newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    meta = op_metadata(paths[-1])
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if is_device_plane(plane.name):
            names = meta.get(plane.name, {})
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [Event(e.start_ns, e.duration_ns, op_name(e.name),
                                  *names.get(e.name, ("", "")))
                            for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.start_ns, e.duration_ns, e.name)
                         for e in line.events
                         if e.name.startswith(("bench.", "slam."))]
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
