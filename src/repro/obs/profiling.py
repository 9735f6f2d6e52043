"""What the program tells the profiler: named scopes on its device work,
and counters of what JAX spent building programs.

Scopes
------
:func:`scoped` runs a function under ``jax.named_scope``.  The name lands
in the ``op_name`` metadata of every HLO operation traced inside it, at no
cost on the device, and survives ``jvp``/``transpose``: the backward of a
scoped stage carries the stage's name as ``transpose(jvp(<name>))``.  A
profiler trace of the chip then gives each device operation its innermost
work scope (``WORK_SCOPES``) and the step phase it ran in (``PHASES``).

Build counters
--------------
A listener on ``jax.monitoring``, installed when this module is imported,
totals the seconds JAX spent tracing, lowering and compiling (or loading
from the persistent cache) programs, and counts them.  :func:`build_counters`
returns the process-wide totals; the difference of two snapshots is what a
stretch of the run built.
"""

from __future__ import annotations

import functools
import threading

import jax
from jax import monitoring

__all__ = ["PHASES", "WORK_SCOPES", "build_counters", "scoped"]

#: Phases of the fused step (``session._make_row_step``).
PHASES = ("slam.track", "slam.map", "slam.keyframe", "slam.paged")

#: Work scopes, on the functions that do the work, so every caller (the
#: fused step, ``step_many``, the served and the unfused path) inherits them.
WORK_SCOPES = ("project", "frag_build", "wsu_schedule", "raster", "loss",
               "adam", "prune", "densify")


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``.

    A fresh scope per call (``jax.named_scope`` used as a decorator shares
    one context object, which nested or concurrent calls would corrupt)."""

    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner

    return deco


# jax.monitoring events, by the counter they feed.
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
          _LOWER: "lower_s",
          # Wraps the persistent-cache lookup too: compiled or loaded.
          "/jax/core/compile/backend_compile_duration": "compile_s"}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


class _Union:
    """Seconds covered by spans that arrive as they end.  A span that
    overlaps earlier ones (an inner jit traced inside an outer one) finds
    them at the end of the list: they merge, and count once."""

    def __init__(self):
        self.spans: list = []          # disjoint, in the order they ended
        self.total = 0.0

    def add(self, start: float, end: float) -> None:
        while self.spans and self.spans[-1][1] > start:
            s, t = self.spans.pop()
            self.total -= t - s
            start, end = min(start, s), max(end, t)
        self.spans.append((start, end))
        self.total += end - start


_lock = threading.Lock()
_unions = {key: _Union() for key in (*_SPANS.values(), "jit_s")}
_totals: dict = {"cache_load_s": 0.0, "programs": 0,
                 **dict.fromkeys(_EVENTS.values(), 0)}


def _on_span(event: str, start: float, end: float, **_):
    key = _SPANS.get(event)
    if key is not None:
        with _lock:
            _unions[key].add(start, end)
            _unions["jit_s"].add(start, end)


def _on_duration(event: str, secs: float, **_):
    with _lock:
        if event == _LOWER:
            _totals["programs"] += 1
        elif event == _CACHE_LOAD:
            _totals["cache_load_s"] += secs


def _on_event(event: str, **_):
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _totals[key] += 1


def build_counters() -> dict:
    """Process-wide totals since import: ``trace_s``, ``lower_s``,
    ``compile_s`` (compiled or loaded from the persistent cache) and
    ``jit_s`` (their union) in seconds, ``cache_load_s`` (inside
    ``compile_s``), and the counts ``programs`` (programs lowered, whether
    then compiled or loaded), ``cache_hits`` and ``cache_misses``."""
    with _lock:
        return {**{k: u.total for k, u in _unions.items()}, **_totals}


monitoring.register_event_time_span_listener(_on_span)
monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)
