"""Device time of the keyframe mapping phase (the ``slam.map`` phase scope:
densify, window builds, the mapping scan and the eval render, kernels
included) in the window, per frame over all frames (one in four maps), by
``bench/scopes.py``'s attribution of the trace."""

from bench import scopes


def read(run):
    d = scopes.split(run)
    return None if d is None else d["phases"]["slam.map"]
