"""Seconds of set-up spent building programs: tracing, lowering, and
compiling or loading from the persistent cache, as the union of those
spans (the program's ``build_counters()`` at the end of set-up)."""


def read(run):
    s = run.counters["setup"]["jit_s"]
    return s if s > 0 else None
