"""SlamScope — zero-overhead telemetry for the RTGS serving stack.

Three layers (see each module's docstring), and the profiler's names:

* :mod:`repro.obs.registry` — counters, gauges, log-bucketed latency
  histograms (mergeable, per-stream labels).
* :mod:`repro.obs.trace` — the single wall-clock definition
  (:func:`now_s`/:class:`Stopwatch`) and span tracing with Perfetto-loadable
  Chrome-trace-event JSON export.
* :mod:`repro.obs.hooks` — the :class:`Telemetry` sink protocol threaded
  through engine → session → server → benchmarks.
* :mod:`repro.obs.profiling` — named scopes on the device work and build
  counters, for profiler traces of the chip.

The load-bearing invariant: telemetry rides data the host already has
(wall-clock stamps, queue lengths, already-fetched ``DeviceWork``), so a
telemetry-on run is bitwise-identical to a telemetry-off run and the
serving tier keeps exactly 1.0 dispatches/frame-step
(tests/test_obs.py).
"""

from repro.obs.hooks import (
    TELEMETRY_OFF,
    Telemetry,
    latency_summary,
    telemetry_or_off,
)
from repro.obs.registry import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import Stopwatch, TraceRecorder, now_s

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Stopwatch",
    "TELEMETRY_OFF",
    "Telemetry",
    "TraceRecorder",
    "latency_summary",
    "now_s",
    "telemetry_or_off",
]
