"""WSU: Workload Scheduling Unit — execution schedules for the rasterizer.

RTGS's third hardware pillar mitigates workload imbalance "via subtile-level
streaming and pixel-level pairwise scheduling guided by previous iteration
information".  This module is its software form: it turns the previous
iteration's per-tile fragment counts (``FragmentLists.count``) into a
:class:`TileSchedule` the Pallas kernels consume via scalar prefetch:

* **pairwise scheduling** — tiles are argsorted by fragment count and the
  heaviest is folded onto the lightest (``sorting.balanced_pair_permutation``)
  so each grid program processes one *balanced pair* of tiles.  Per-program
  fragment load concentrates at ~2x the mean instead of spanning
  [0, max-tile]; the tail program no longer sets the wall clock.
* **subtile streaming** — each slot carries a chunk *trip count* derived from
  its actual load (optionally rounded up to ``bucket`` trips so tiles fall
  into a few load buckets), and the kernels loop ``lax.fori_loop(0, trips)``
  instead of the full ``capacity // chunk`` trips.  Light tiles stop early by
  construction, not via ``pl.when`` skips over dead chunks.
* **previous-iteration reuse** — a schedule is a pure function of
  ``count``, so the engine carries it through its ``lax.scan`` next to the
  cached ``FragmentLists`` and rebuilds it only on the existing rebuild
  boundaries (§4.1 interval updates, mapping stride).  Scheduling costs zero
  extra host syncs and zero extra dispatches.

The schedule is exact: pair programs replay the same per-tile chunk sequence
as the unscheduled kernel, and trips only drop chunks whose contribution is
identically zero, so scheduled rendering is *bit-identical* to the
unscheduled Pallas path (tests/test_schedule.py holds this under arbitrary
permutations).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from repro.core.sorting import balanced_pair_permutation
from repro.obs.profiling import scoped


class TileSchedule(NamedTuple):
    """An execution schedule over ``S = 2 * ceil(T / 2)`` slots (= S/2 pairs).

    Slot ``i`` renders tile ``perm[i]``; slots ``2p`` and ``2p+1`` form pair
    ``p`` and run in one kernel program.  Kernel outputs are emitted in slot
    (schedule) order and un-permuted with ``inv``.  All fields are device
    arrays so a schedule can live in a ``lax.scan`` carry.
    """

    perm: jnp.ndarray   # (S,) int32 slot -> tile id (one tile may repeat as pad)
    inv: jnp.ndarray    # (T,) int32 tile -> slot of its *working* occurrence
    trips: jnp.ndarray  # (S,) int32 chunk trips the slot actually runs
    load: jnp.ndarray   # (S,) int32 fragment count the slot owes (0 for pad)


def _inverse_slots(perm: jnp.ndarray, num_tiles: int) -> jnp.ndarray:
    """tile -> slot.  With an odd tile count, ``perm`` holds a zero-work
    duplicate of the lightest tile in slot 1 (see
    ``balanced_pair_permutation``); scatter-max with that slot demoted to -1
    makes the tile resolve to its working slot regardless of scatter order."""
    s = perm.shape[0]
    slots = jnp.arange(s, dtype=jnp.int32)
    if s != num_tiles:
        slots = jnp.where(slots == 1, -1, slots)
    return jnp.full((num_tiles,), -1, jnp.int32).at[perm].max(slots)


@scoped("wsu_schedule")
def build_schedule(
    count: jnp.ndarray,
    chunk: int,
    *,
    bucket: int = 1,
    max_trips: Optional[int] = None,
) -> TileSchedule:
    """Build the pairwise schedule from per-tile fragment counts.

    ``bucket`` rounds trip counts up to multiples of ``bucket`` (load
    bucketing: fewer distinct trip counts keeps the streamed pipeline more
    regular on real hardware); ``max_trips`` clamps the rounding at the
    capacity bound.  Pure jnp, jit/scan-safe.
    """
    t = count.shape[0]
    perm, load = balanced_pair_permutation(count)
    trips = (load + chunk - 1) // chunk
    if bucket > 1:
        # Rounding up needs the capacity bound or the kernels would stream
        # chunks past the fragment block (silently clamped slices).
        assert max_trips is not None, "bucket > 1 requires max_trips"
        trips = ((trips + bucket - 1) // bucket) * bucket
        trips = jnp.where(load > 0, trips, 0)
    if max_trips is not None:
        trips = jnp.minimum(trips, max_trips)
    return TileSchedule(
        perm=perm,
        inv=_inverse_slots(perm, t),
        trips=trips.astype(jnp.int32),
        load=load,
    )


def schedule_from_order(perm: jnp.ndarray, count: jnp.ndarray, chunk: int) -> TileSchedule:
    """Schedule an *arbitrary* even-length tile permutation (every tile
    exactly once; consecutive slots pair up).  Exists for ablations and for
    the permutation-invariance property tests — pairing quality is the
    caller's problem."""
    t = count.shape[0]
    assert perm.shape[0] == t and t % 2 == 0, "need an even #tiles permutation"
    perm = perm.astype(jnp.int32)
    load = count[perm].astype(jnp.int32)
    inv = jnp.zeros((t,), jnp.int32).at[perm].set(jnp.arange(t, dtype=jnp.int32))
    trips = (load + chunk - 1) // chunk
    return TileSchedule(perm=perm, inv=inv, trips=trips.astype(jnp.int32), load=load)


def pair_loads(sched: TileSchedule) -> jnp.ndarray:
    """Fragment load per pair program, (S/2,) — the quantity pairing
    balances and the imbalance counters report on."""
    return sched.load.reshape(-1, 2).sum(axis=1)


def active_programs(sched: TileSchedule) -> jnp.ndarray:
    """() int32 — pair programs with nonzero trips, i.e. programs that
    actually stream fragments.  XLA's grid is static, so the sparse
    stable/unstable path can't literally launch fewer programs; a zero-trip
    pair's ``fori_loop(0, 0)`` body never runs, so this count is the honest
    software proxy for the shrunken grid a real WSU would launch (same
    provisioned-vs-streamed convention as the WSU trip counters)."""
    pair_trips = sched.trips.reshape(-1, 2).sum(axis=1)
    return jnp.sum((pair_trips > 0).astype(jnp.int32))


def active_tile_programs(count: jnp.ndarray) -> jnp.ndarray:
    """() int32 — tiles with nonzero fragment count: the per-tile-program
    analogue of :func:`active_programs` for the unscheduled backends (tile
    and interpret-mode Pallas), where one program owns one tile."""
    return jnp.sum((count > 0).astype(jnp.int32))


def scheduled_trips(sched: TileSchedule) -> jnp.ndarray:
    """() int32 — total chunk trips the schedule streams: the **subtile
    program** count in the WSU's subtile-level streaming model, where each
    chunk trip is one scheduled unit of raster work.  This is the
    granularity at which stable/unstable sparsity is visible: pairing folds
    empty tiles onto loaded ones, so :func:`active_programs` (pair
    granularity) only drops when BOTH tiles of a pair are empty — on small
    grids that almost never happens — while a stable-only tile's trips drop
    to zero immediately and the total tracks streamed work."""
    return jnp.sum(sched.trips)


def tile_trips(count: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """() int32 — :func:`scheduled_trips` for the unscheduled backends: the
    chunk trips a per-tile capacity loop would actually need (``ceil(count
    / chunk)`` per tile), i.e. the same subtile-program unit without the
    pairing."""
    return jnp.sum((count + chunk - 1) // chunk)
