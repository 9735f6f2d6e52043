"""Device time of the projection (the ``project`` work scope:
``core/projection.project``, forward and backward) in the window, per
frame, by ``bench/scopes.py``'s attribution of the trace."""

from bench import scopes


def read(run):
    d = scopes.split(run)
    return None if d is None else d["work"]["project"]
