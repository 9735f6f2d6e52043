"""SlamServe — device-sharded, queue-fed multi-session SLAM serving tier.

SlamSession v1 (PR 4) collapsed S concurrent streams into one stacked
pytree and ONE dispatch per frame-step — but only on a single device, fed
by a synchronous host loop.  This module is the serving layer above it:

* :class:`ShardedPool` lays the stacked session's rows out across a device
  mesh with ``NamedSharding`` on the ``"data"`` axis (the
  ``launch/mesh.py`` + ``distributed/sharding.py`` conventions), so the
  same single ``step_many`` executable serves S sessions on D devices with
  donated state buffers.  Per-row computation is the identical trace as a
  solo :func:`~repro.slam.session.session_step` (the jitted function comes
  from :func:`~repro.slam.session.make_many_step`, shared with
  ``step_many``), so **every row stays bitwise-equal to its solo run** —
  sharding changes where rows compute, never what they compute
  (tests/test_serve.py proves it on a forced 8-device host).

* :class:`FrameQueue` + :class:`SlamServer` form the asynchronous host
  pipeline: per-stream bounded ingest queues with backpressure, a
  dispatcher that stages each lockstep frame batch onto the row sharding
  and fires the step **asynchronously** (JAX async dispatch returns as
  soon as the work is enqueued), so host staging of batch t+1 overlaps
  device compute of batch t.  The host blocks on the device only in
  :meth:`SlamServer.drain` / ``finalize`` — the ~1 sync/run property of
  the session tier survives the serving tier.

* Admission control: :meth:`SlamServer.admit` / :meth:`SlamServer.retire`
  swap pytree rows in place across the shards mid-stream (one cached
  slot-traced executable), so heterogeneous scenes run concurrently and
  finished streams hand their slots to waiting ones.  A full pool raises
  :class:`PoolFull` — admission backpressure — and full ingest queues
  push back through :meth:`SlamServer.submit`.

Free slots (retired, not yet re-admitted) keep stepping on blank frames —
the stacked executable is lockstep by construction — and their row state
is scratch until the next ``admit`` overwrites every leaf.

Serving constraints are the session tier's
(:func:`~repro.slam.session.require_servable`): ``cfg.fused=True``,
downsampling off; additionally S must divide evenly over the mesh's
``"data"`` axis.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.lie import f32_jit
from repro.distributed.sharding import to_shardings
from repro.launch.mesh import axis_size, make_data_mesh
from repro.obs import Stopwatch, Telemetry, now_s, telemetry_or_off
from repro.slam.engine import EngineStats
from repro.slam.session import (
    Observation,
    SLAMResult,
    SlamSession,
    StepResult,
    make_many_step,
    require_servable,
    session_finalize,
    session_row,
    session_step_key,
    stack_observations,
    stack_sessions,
    validate_admission,
)


class PoolFull(RuntimeError):
    """Admission backpressure: every slot is live; retire one first."""


class QueueFull(RuntimeError):
    """Ingest backpressure: a stream is ahead of its lockstep peers and
    its bounded queue cannot absorb more frames."""


# ---------------------------------------------------------------------------
# the sharded device pool
# ---------------------------------------------------------------------------

_SERVE_STEP_CACHE: dict = {}
_SERVE_SWAP_CACHE: dict = {}


def _jit_traces(fns) -> int:
    """Total traced-signature count across jitted callables (0 where the
    jax version doesn't expose ``_cache_size``)."""
    return sum(getattr(f, "_cache_size", lambda: 0)() for f in fns)


def compile_cache_stats() -> dict:
    """Executable-cache census across the serving stack: entry counts of the
    serve step/swap caches and the session-tier step/boot caches, plus the
    per-jit traced-signature totals.  The sched tier's **zero-recompile
    invariant** is measured as this dict being EQUAL before and after a
    serving phase (admissions, migrations and steps included) — any retrace
    or new executable shows up as a changed number."""
    from repro.slam import session as _session

    return {
        "serve_step_entries": len(_SERVE_STEP_CACHE),
        "serve_swap_entries": len(_SERVE_SWAP_CACHE),
        "serve_step_traces": _jit_traces(_SERVE_STEP_CACHE.values()),
        "serve_swap_traces": _jit_traces(_SERVE_SWAP_CACHE.values()),
        "session_step_entries": len(_session._STEP_CACHE),
        "session_step_traces": _jit_traces(_session._STEP_CACHE.values()),
        "session_boot_entries": len(_session._BOOT_CACHE),
        "session_boot_traces": _jit_traces(_session._BOOT_CACHE.values()),
    }


class ShardedPool:
    """S stacked sessions laid out over D devices, stepped by ONE dispatch.

    The stacked :class:`SlamSession` pytree is placed with
    ``NamedSharding(mesh, P("data"))`` on every leaf's leading S axis, so
    each device owns S/D complete session rows.  :meth:`step` runs the
    shared ``make_many_step`` trace on each device's own rows
    (``shard_map``; session state buffers donated) — one executable, one
    dispatch per frame-step, rows bitwise-equal to single-device
    ``step_many``.  :meth:`swap` is the admission tier's device op: replace
    one row across the shards via a slot-traced cached executable.
    """

    def __init__(self, sessions: Sequence[SlamSession], mesh=None):
        sessions = list(sessions)
        if not sessions:
            raise ValueError("ShardedPool needs at least one session")
        self.mesh = mesh if mesh is not None else make_data_mesh()
        if "data" not in self.mesh.axis_names:
            raise ValueError("ShardedPool mesh needs a 'data' axis; got "
                             f"axes {self.mesh.axis_names}")
        d = axis_size(self.mesh, "data")
        if len(sessions) % d != 0:
            raise ValueError(
                f"pool size {len(sessions)} must divide evenly over the "
                f"{d}-device 'data' axis (rows shard whole, never split)")
        require_servable(sessions[0].meta.cfg, what="ShardedPool")
        # One NamedSharding, applied to every leaf as a pytree prefix:
        # leading S axis on "data", everything else replicated within a row.
        self.sharding = to_shardings(self.mesh, P("data"))
        # Canonical placement for solo rows crossing the pool boundary
        # (admit input / retire output): replicated on this pool's mesh.
        # Pinning it keeps the swap executable's input signature stable no
        # matter where a row comes from — a fresh host-side session_init or
        # a row gathered out of ANOTHER pool by the sched tier's migration
        # — so admission never retraces (the zero-recompile invariant).
        self.row_sharding = to_shardings(self.mesh, P())
        self._stacked = jax.device_put(stack_sessions(sessions),
                                       self.sharding)
        self.stats = EngineStats()     # step dispatches / result syncs
        self.admin_dispatches = 0      # admit/retire row swaps

    # -- introspection -----------------------------------------------------

    @property
    def size(self) -> int:
        return self._stacked.batch

    @property
    def num_devices(self) -> int:
        return axis_size(self.mesh, "data")

    @property
    def meta(self):
        return self._stacked.meta

    @property
    def stacked(self) -> SlamSession:
        return self._stacked

    def session(self, slot: int) -> SlamSession:
        """Row ``slot`` as a solo session (lazy gather across shards)."""
        return session_row(self._stacked, slot)

    def _cache_key(self):
        # Mesh structure matters, not just the device set: the same devices
        # reshaped under different axes produce different NamedShardings,
        # and the jitted executables bake self.sharding in.
        dev_ids = tuple(int(dv.id) for dv in self.mesh.devices.flat)
        return (dev_ids, self.mesh.devices.shape, self.mesh.axis_names,
                session_step_key(self.meta, 1, self.size))

    # -- the data plane ----------------------------------------------------

    def stage(self, frames) -> Observation:
        """Host→device staging of one lockstep frame batch onto the row
        sharding.  Asynchronous: overlaps any in-flight step dispatch."""
        obs = stack_observations(frames, self.size)
        return jax.device_put(obs, self.sharding)

    def step(self, frames) -> StepResult:
        """Advance all S rows by one frame: ONE dispatch of the shared
        sharded executable.  ``frames`` is S per-row frames or an already
        :meth:`stage`-d ``Observation``."""
        obs = self.stage(frames)
        key = ("serve-step",) + self._cache_key()
        if key not in _SERVE_STEP_CACHE:
            # Each device steps its own S/D rows: the Pallas kernels inside
            # cannot be partitioned by the compiler, so the row trace runs
            # per shard.
            rows = P("data")
            local = jax.shard_map(
                make_many_step(self.meta, self.size // self.num_devices),
                mesh=self.mesh, in_specs=(rows, rows),
                out_specs=(rows, rows), check_vma=False)
            _SERVE_STEP_CACHE[key] = f32_jit(
                local,
                in_shardings=(self.sharding, self.sharding),
                out_shardings=(self.sharding, self.sharding),
                donate_argnums=0)
        self.stats.dispatches += 1
        self._stacked, res = _SERVE_STEP_CACHE[key](self._stacked, obs)
        return res

    # -- the control plane -------------------------------------------------

    def swap(self, slot: int, new_session: SlamSession) -> SlamSession:
        """Replace row ``slot`` across the shards with ``new_session`` and
        return the retired row as a solo session.  One cached slot-traced
        executable serves every slot (counted in ``admin_dispatches``, not
        the per-frame-step ``stats``)."""
        validate_admission(new_session, self._stacked)
        new_session = jax.device_put(new_session, self.row_sharding)
        key = ("serve-swap",) + self._cache_key()
        if key not in _SERVE_SWAP_CACHE:
            def swap(stacked, row, slot_ix):
                old = jax.tree.map(
                    lambda buf: jax.lax.dynamic_index_in_dim(
                        buf, slot_ix, 0, keepdims=False), stacked)
                new = jax.tree.map(
                    lambda buf, r: jax.lax.dynamic_update_index_in_dim(
                        buf, r, slot_ix, 0), stacked, row)
                return new, old

            _SERVE_SWAP_CACHE[key] = jax.jit(
                swap,
                in_shardings=(self.sharding, self.row_sharding, None),
                out_shardings=(self.sharding, self.row_sharding),
                donate_argnames="stacked")
        self.admin_dispatches += 1
        self._stacked, old = _SERVE_SWAP_CACHE[key](
            self._stacked, new_session, jnp.asarray(slot, jnp.int32))
        return old

    def finalize(self, slot: int, gt_w2c=None, **kw) -> SLAMResult:
        return session_finalize(self.session(slot), gt_w2c=gt_w2c,
                                stats=self.stats, **kw)

    def memory_profile(self) -> dict:
        """Static per-row memory shape of this pool — the PagedMap serving
        story in numbers.  ``storage_rows`` is each row's full Gaussian
        pool; ``working_rows`` is the rows a frame-step actually optimizes
        (the frustum-culled view when ``cfg.paged`` is set, the whole pool
        otherwise), and the byte figures scale them by the per-row leaf
        width, so the pool-wide optimizer traffic is bounded by
        ``size * working_bytes`` regardless of total map size."""
        cfg = self.meta.cfg
        storage_rows = cfg.capacity
        paged = getattr(cfg, "paged", None)
        working_rows = (paged.visible_pages * paged.page_capacity
                        if paged is not None else storage_rows)
        # Bytes per Gaussian row: stacked g leaves are (S, N, ...), so the
        # trailing dims x itemsize of each leaf is its per-row width.
        row_bytes = sum(int(np.prod(leaf.shape[2:], dtype=np.int64))
                        * leaf.dtype.itemsize
                        for leaf in jax.tree.leaves(self._stacked.g))
        return {
            "rows": self.size,
            "storage_rows": storage_rows,
            "working_rows": working_rows,
            "working_fraction": working_rows / storage_rows,
            "storage_bytes_per_row": storage_rows * row_bytes,
            "working_bytes_per_row": working_rows * row_bytes,
            "paged": paged is not None,
        }


# ---------------------------------------------------------------------------
# the host-side frame pipeline
# ---------------------------------------------------------------------------


#: Flow ids are allocated process-globally (not per queue) so a trace fed
#: by several queues — the sched tier runs one FrameQueue per pool group —
#: never reuses an arrow id, and a frame migrated between queues keeps the
#: arrow it opened at first enqueue.  ``itertools.count`` is atomic under
#: the GIL, so producer threads share it without a lock.
_FLOW_IDS = itertools.count()


class FrameQueue:
    """Bounded per-slot frame staging queues (host memory only).

    ``put`` returns ``False`` when a slot's queue is at depth — the
    caller's backpressure signal.  Enqueue timestamps (``obs.now_s``, the
    codebase's one wall clock) and a flow id ride along so the dispatcher
    can account queue wait per frame AND draw the enqueue→dispatch flow
    arrow in the trace.  The telemetry sink sees every depth change
    (``queue_depth`` gauge per slot — its ``hwm`` is the queue-depth
    high-water mark BENCH reports).

    Thread-safe: every mutation (``put``/``pop``/``fill``/``clear``/
    ``take``/``load``) and the ``ready`` check hold one internal lock, and
    the depth gauge updates ride inside it — the sched tier's ingest worker
    produces from its own thread while the dispatch thread consumes.
    """

    def __init__(self, slots: int, depth: int = 2,
                 telemetry: Optional[Telemetry] = None):
        if depth < 1:
            raise ValueError(f"queue depth must be >= 1, got {depth}")
        self.depth = depth
        self.tele = telemetry_or_off(telemetry)
        self._q: List[collections.deque] = [
            collections.deque() for _ in range(slots)]
        self._lock = threading.Lock()

    def _depth_changed(self, slot: int) -> None:
        n = len(self._q[slot])
        self.tele.gauge("queue_depth", n, slot=slot)
        self.tele.trace.counter(f"queue_depth/slot{slot}", depth=n)

    def put(self, slot: int, frame) -> bool:
        with self._lock:
            q = self._q[slot]
            if len(q) >= self.depth:
                return False
            fid = next(_FLOW_IDS)
            q.append((frame, now_s(), fid))
            self.tele.flow_start(fid, "frame")
            self._depth_changed(slot)
            return True

    def pop(self, slot: int):
        """Oldest queued ``(frame, waited_s, flow_id)`` for ``slot``."""
        with self._lock:
            frame, t0, fid = self._q[slot].popleft()
            self._depth_changed(slot)
        return frame, now_s() - t0, fid

    def fill(self, slot: int) -> int:
        with self._lock:
            return len(self._q[slot])

    def clear(self, slot: int) -> int:
        with self._lock:
            n = len(self._q[slot])
            self._q[slot].clear()
            if n:
                self._depth_changed(slot)
            return n

    def ready(self, slots) -> bool:
        """True when every listed slot has a frame queued — a lockstep
        batch can dispatch."""
        with self._lock:
            return all(self._q[s] for s in slots)

    def head_age_s(self, slot: int) -> Optional[float]:
        """Seconds the oldest queued frame of ``slot`` has been waiting
        (the scheduler policy's oldest-deadline signal), or None when
        empty."""
        with self._lock:
            q = self._q[slot]
            return (now_s() - q[0][1]) if q else None

    # -- migration support (the sched tier's queue transplant) -------------

    def take(self, slot: int) -> List[Tuple]:
        """Drain ``slot``'s raw entries — ``(frame, enqueue_ts, flow_id)``
        triples with their ORIGINAL timestamps and flow ids — so a row
        migration can transplant them into the destination pool's queue
        without dropping frames, resetting waits, or breaking trace
        arrows."""
        with self._lock:
            q = self._q[slot]
            entries = list(q)
            q.clear()
            if entries:
                self._depth_changed(slot)
            return entries

    def load(self, slot: int, entries: Sequence[Tuple]) -> None:
        """Requeue entries previously ``take``-n from a source queue, at
        the head-preserving order.  The destination slot must be empty and
        the batch must fit the depth bound (migrations move whole queues
        between equal-depth queues, so this never triggers in practice)."""
        if not entries:
            return
        with self._lock:
            q = self._q[slot]
            if q:
                raise ValueError(f"slot {slot} is not empty "
                                 f"({len(q)} frames); cannot load into it")
            if len(entries) > self.depth:
                raise ValueError(f"{len(entries)} entries exceed queue "
                                 f"depth {self.depth}")
            q.extend(entries)
            self._depth_changed(slot)


@dataclasses.dataclass
class ServeStats:
    """Host-observable serving pipeline counters (the device-side
    dispatch/sync counters live on ``ShardedPool.stats``)."""

    steps: int = 0                 # lockstep frame-steps dispatched
    frames_in: int = 0             # frames accepted by submit()
    frames_dropped: int = 0        # queued frames discarded by retire()
    admits: int = 0
    retires: int = 0
    backpressure_events: int = 0   # submits that hit a full queue
    queue_wait_s: float = 0.0      # total enqueue->dispatch latency
    stage_s: float = 0.0           # host time staging batches

    @property
    def queue_wait_ms_per_frame(self) -> float:
        n = max(self.frames_in - self.frames_dropped, 1)
        return 1e3 * self.queue_wait_s / n


class SlamServer:
    """The queue-fed dispatcher over a :class:`ShardedPool`.

    Streams ``submit`` frames into bounded per-slot queues; ``pump``
    dispatches one lockstep frame-step whenever every live slot has a
    frame queued.  Dispatch is asynchronous — the jitted call returns as
    soon as XLA enqueues the work — so the host immediately moves on to
    staging the next batch (``np.stack`` + sharded ``device_put``) while
    the devices compute.  Only :meth:`drain` blocks.

    ``admit``/``retire`` are the admission tier: retire snapshots a row as
    a solo session and frees the slot (blank frames keep the lockstep
    shape; the row's leftover state is scratch), admit overwrites a free
    slot's every leaf with a fresh session.  A full pool raises
    :class:`PoolFull`.

    ``telemetry`` (SlamScope) instruments the pump as spans (``stage``,
    ``dispatch``, ``drain``, ``admit``, ``retire``) with an
    enqueue→dispatch flow arrow per frame, and feeds the registry
    per-stream ``frame_latency_ms``/``queue_wait_ms`` histograms, the
    ``queue_depth`` gauges, and ``dispatches`` counters split by
    ``kind="step"`` vs ``kind="admin"``.  Everything rides host-side
    values the server already holds — telemetry on/off runs are
    bitwise-identical with exactly the same dispatch count
    (tests/test_obs.py).
    """

    def __init__(self, pool: ShardedPool, queue_depth: int = 2,
                 live: Optional[Sequence[int]] = None,
                 telemetry: Optional[Telemetry] = None, name: str = ""):
        self.pool = pool
        self.name = name
        # Per-group label on the kind-split dispatch counters, so a ladder
        # of servers sharing one registry stays measurable per group.  A
        # nameless (v1) server keeps the unlabeled series.
        self._glab = {"group": name} if name else {}
        self.tele = telemetry_or_off(telemetry)
        self.queue = FrameQueue(pool.size, queue_depth, telemetry=self.tele)
        self.stats = ServeStats()
        self._live = [False] * pool.size
        for s in (range(pool.size) if live is None else live):
            self._live[s] = True
        # Telemetry stream label per slot — defaults to the slot index (the
        # v1 convention); the sched tier relabels on admit so a stream's
        # latency series survives row migrations between pools.
        self._labels: List = list(range(pool.size))
        intr = pool.meta.intr
        self._blank = (np.zeros((intr.height, intr.width, 3), np.float32),
                       np.zeros((intr.height, intr.width), np.float32))
        self.last_result: Optional[StepResult] = None

    # -- introspection -----------------------------------------------------

    def live_slots(self) -> List[int]:
        return [s for s, lv in enumerate(self._live) if lv]

    def free_slots(self) -> List[int]:
        return [s for s, lv in enumerate(self._live) if not lv]

    def slot_label(self, slot: int):
        """The telemetry ``stream=`` label of ``slot``."""
        return self._labels[slot]

    def label_slot(self, slot: int, label) -> None:
        """Relabel ``slot``'s telemetry stream series (sched tier: stream
        ids follow sessions across migrations; slots are transient)."""
        self._labels[slot] = label

    # -- ingest ------------------------------------------------------------

    def submit(self, slot: int, frame) -> None:
        """Queue one frame for ``slot``.  On a full queue, backpressure:
        pump (dispatching any ready lockstep batches) to make room; if the
        queue is still full — this stream is ahead of a starved peer —
        raise :class:`QueueFull`."""
        if not self._live[slot]:
            raise ValueError(f"slot {slot} is not live; admit a session "
                             "first")
        with self.tele.span("submit", slot=slot):
            if not self.queue.put(slot, frame):
                self.stats.backpressure_events += 1
                self.tele.count("backpressure", stream=self._labels[slot])
                self.pump()
                if not self.queue.put(slot, frame):
                    raise QueueFull(
                        f"slot {slot}'s queue is at depth "
                        f"{self.queue.depth} and no lockstep batch can "
                        "dispatch (a peer stream is starved); submit "
                        "frames for the other live slots")
            self.stats.frames_in += 1

    def offer(self, slot: int, frame) -> bool:
        """Non-blocking ingest: queue one frame for ``slot`` if its queue
        has room, else return ``False`` — and NEVER pump.  This is the
        producer-thread entry point (the sched tier's ingest worker calls
        it off the dispatch thread; dispatching from a producer thread
        would race the dispatcher), so unlike :meth:`submit` it must not
        issue device work under backpressure."""
        if not self._live[slot]:
            raise ValueError(f"slot {slot} is not live; admit a session "
                             "first")
        if not self.queue.put(slot, frame):
            self.stats.backpressure_events += 1
            self.tele.count("backpressure", stream=self._labels[slot])
            return False
        self.stats.frames_in += 1
        return True

    # -- dispatch ----------------------------------------------------------

    def pump(self) -> int:
        """Dispatch as many lockstep frame-steps as the queues allow,
        asynchronously (never blocks on device compute).  Returns the
        number of steps dispatched.

        Telemetry per step: a ``stage`` span (frame pops + sharded
        ``device_put``) and a ``dispatch`` span (the async jitted call)
        with each popped frame's flow arrow ending inside it; per-frame
        ``queue_wait_ms`` and ``frame_latency_ms`` (enqueue→dispatch-return
        — the host-observable latency of an async pipeline; device-time is
        only knowable at :meth:`drain`) land in per-stream histograms."""
        live = self.live_slots()
        steps = 0
        while live and self.queue.ready(live):
            step_no = self.stats.steps
            sw = Stopwatch()
            rows, popped = [], []
            with self.tele.span("stage", step=step_no):
                for s in range(self.pool.size):
                    if self._live[s]:
                        frame, waited, fid = self.queue.pop(s)
                        self.stats.queue_wait_s += waited
                        self.tele.latency("queue_wait_ms", waited * 1e3,
                                          stream=self._labels[s])
                        popped.append((s, now_s() - waited, fid))
                        rows.append(frame)
                    else:
                        rows.append(self._blank)
                obs = self.pool.stage(rows)
            self.stats.stage_s += sw.elapsed()
            with self.tele.span("dispatch", step=step_no, **self._glab):
                for _, _, fid in popped:
                    self.tele.flow_end(fid, "frame")
                self.last_result = self.pool.step(obs)
            self.tele.count("dispatches", kind="step", **self._glab)
            t1 = now_s()
            for s, t_enq, _ in popped:
                self.tele.latency("frame_latency_ms", (t1 - t_enq) * 1e3,
                                  stream=self._labels[s])
            self.tele.latency("step_host_ms", sw.elapsed() * 1e3)
            self.stats.steps += 1
            steps += 1
        return steps

    def drain(self) -> None:
        """Pump the remaining ready batches, then block until every
        in-flight dispatch finishes — the ONE device sync of a serving
        run."""
        self.pump()
        with self.tele.span("drain"):
            jax.block_until_ready(jax.tree.leaves(self.pool.stacked))
        self.pool.stats.syncs += 1
        self.tele.count("syncs")

    # -- admission control -------------------------------------------------

    def admit(self, session: SlamSession, label=None) -> int:
        """Place ``session`` in the first free slot (one row swap across
        the shards) and mark it live.  Raises :class:`PoolFull` when every
        slot is serving — the admission backpressure signal.  ``label``
        names the slot's telemetry stream series (default: the slot
        index)."""
        free = self.free_slots()
        if not free:
            raise PoolFull(
                f"all {self.pool.size} slots are live; retire a session "
                "first (admission backpressure)")
        slot = free[0]
        with self.tele.span("admit", slot=slot, **self._glab):
            self.pool.swap(slot, session)
        self.tele.count("dispatches", kind="admin", **self._glab)
        # A free slot's queue is empty in normal operation (retire clears
        # it and dead slots refuse submits), but any straggler frames a
        # caller managed to park there must not leak into the new stream —
        # drop and account them like retire does.
        self.stats.frames_dropped += self.queue.clear(slot)
        self._live[slot] = True
        self._labels[slot] = slot if label is None else label
        self.stats.admits += 1
        return slot

    def retire(self, slot: int) -> SlamSession:
        """Snapshot ``slot``'s row as a solo session and free the slot.
        Queued-but-undispatched frames for the slot are dropped (counted
        in ``stats.frames_dropped``; a migration that must NOT drop them
        ``queue.take``-s the entries first and ``load``-s them into the
        destination queue)."""
        if not self._live[slot]:
            raise ValueError(f"slot {slot} is not live")
        self.stats.frames_dropped += self.queue.clear(slot)
        self._live[slot] = False
        self.stats.retires += 1
        with self.tele.span("retire", slot=slot, **self._glab):
            row = self.pool.session(slot)
        return row

    def finalize(self, slot: int, gt_w2c=None, **kw) -> SLAMResult:
        """Drain and assemble ``slot``'s :class:`SLAMResult` (syncs)."""
        self.drain()
        return self.pool.finalize(slot, gt_w2c=gt_w2c, **kw)
