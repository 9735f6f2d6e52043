"""Share of the window in which no operation ran on the device (the union
of the device's operation intervals, averaged over the chips)."""

from bench import tracing


def read(run):
    return tracing.idle_share(run.trace)
