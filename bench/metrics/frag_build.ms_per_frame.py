"""Device time of the fragment-list builds (the ``frag_build`` work scope:
``core/sorting.build_fragment_lists``, ``count_skipped_fragments``,
``update_fragment_slot``) in the window, per frame, by
``bench/scopes.py``'s attribution of the trace."""

from bench import scopes


def read(run):
    d = scopes.split(run)
    return None if d is None else d["work"]["frag_build"]
