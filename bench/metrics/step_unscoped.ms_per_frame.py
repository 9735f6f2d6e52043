"""Device time outside the Pallas kernels that none of the four work
buckets (projection, fragment build, rasterizer glue, optimizer) holds, per
frame: the operations with no work scope, and the WSU schedule's
(``wsu_schedule``, too small for a metric of its own).  So this and the
four buckets add up to ``step_xla.ms_per_frame``."""

from bench import scopes


def read(run):
    d = scopes.split(run)
    if d is None:
        return None
    return d["work"]["step_unscoped"] + d["work"]["wsu_schedule"]
