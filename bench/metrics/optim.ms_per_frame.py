"""Device time of the map's optimizer and its upkeep (the ``adam``,
``prune`` and ``densify`` work scopes) in the window, per frame, by
``bench/scopes.py``'s attribution of the trace."""

from bench import scopes


def read(run):
    d = scopes.split(run)
    return None if d is None else d["work"]["optim"]
