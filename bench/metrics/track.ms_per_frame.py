"""Device time of the tracking phase (the ``slam.track`` phase scope: the
pre-track fragment build and the tracking scan, kernels included) in the
window, per frame, by ``bench/scopes.py``'s attribution of the trace."""

from bench import scopes


def read(run):
    d = scopes.split(run)
    return None if d is None else d["phases"]["slam.track"]
