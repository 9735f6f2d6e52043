"""Host time inside the benchmark's calls into the program per frame-step
(``session_step``, or ``submit`` + ``pump``), not counting the wait for the
poses: the ``bench.dispatch`` spans of the trace."""

from bench import harness, tracing


def read(run):
    ns = tracing.host_ns(run.trace, harness.SPAN_DISPATCH)
    return ns * 1e-6 / run.steps if ns > 0 else None
