"""SlamSession v1 — the typed session/step API for multi-session SLAM serving.

``run_slam(dataset, cfg)`` was a monolith: one host loop per sequence, one
compile cache per engine, one stream per process.  The remaining redundancy
RTGS has not eliminated is this *system-level* one — every sequence pays its
own dispatch loop, so the engine cannot serve more than one stream.  This
module replaces the monolith with a session pytree plus three entry points:

* :func:`session_init` ``(dataset, cfg) -> SlamSession`` — seed the map,
  bootstrap frame 0's mapping (one dispatch).
* :func:`session_step` ``(session, frame) -> (session, StepResult)`` — ONE
  fused tracking+mapping dispatch per frame: fragment build, the K tracking
  iterations (PR 1 scan bundles, §4.1 pruning boundaries under ``lax.cond``),
  the keyframe decision, densification, the masked-window mapping scan AND
  the PSNR eval all ride in a single jitted call.
* :func:`session_finalize` ``(session) -> SLAMResult`` — one fetch of the
  device-resident trajectory/PSNR/work logs.

Scaling up, :func:`step_many` steps S stacked sessions (leaves gain a
leading S axis via :func:`stack_sessions`) through **one shared XLA
executable and one dispatch per frame-step** — the per-row computation is
the same trace as a solo step, so per-session outputs are bitwise-equal to
solo runs (tests/test_session.py enforces).  :class:`SessionPool` is the
host wrapper that admits/retires sequences by swapping pytree rows.

Session state is ALL dynamic pytree leaves (GaussianField, pose/trajectory,
Adam + PruneState, the fixed-shape keyframe ring, cached FragmentLists +
TileSchedule, DeviceWork counters, the densify PRNG key); everything static
lives in ``SLAMConfig`` and keys the step-executable cache via
``raster_api.static_fingerprint`` — a new session field must be a pytree
leaf, a new config knob is picked up by the cache key automatically.

``runner.run_slam`` survives as a thin warn-once-deprecated wrapper over
these entry points; :func:`run_sequence` is the non-deprecated equivalent.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gaussians as G
from repro.core import lie, pruning
from repro.core.camera import Camera, Intrinsics
from repro.core.downsample import (
    DownsampleConfig,
    downsample_depth,
    downsample_image,
    side_factor,
)
from repro.core.keyframes import KeyframePolicy
from repro.core.lie import f32_jit
from repro.core.losses import psnr as psnr_dev
from repro.core.raster_api import static_fingerprint
from repro.core.render import render
from repro.core.schedule import build_schedule
from repro.core.sorting import (
    FragmentLists,
    remap_fragment_rows,
    stack_fragment_lists,
    update_fragment_slot,
)
from repro.slam import geometric
from repro.slam.datasets import SLAMDataset
from repro.slam.engine import (
    EngineStats,
    StepEngine,
    get_geo_scan,
    get_stage,
    silence,
)
from repro.slam.metrics import (
    DeviceWork,
    WorkCounters,
    ate_rmse,
    device_work_merge,
    device_work_zero,
    wide_work_add,
    wide_work_totals,
    wide_work_zero,
)
from repro.obs import Stopwatch, telemetry_or_off
from repro.obs.profiling import scoped
from repro.slam.map import paged as pagedmap
from repro.train import optimizer as optim
from repro.train.optimizer import Adam, AdamState


# ---------------------------------------------------------------------------
# configuration + result types (moved here from runner.py; runner re-exports)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SLAMConfig:
    base_algo: str = "monogs"       # monogs | gsslam | photoslam | splatam
    iters_track: int = 12
    iters_map: int = 24
    lr_pose: float = 3e-3
    lr_map: float = 8e-3
    lambda_pho: float = 0.8
    capacity: int = 8192            # Gaussian pool size
    frag_capacity: int = 128        # K fragments per tile
    backend: str = "ref"            # rasterizer backend (ref is CPU-fast;
                                    # "schedule" = WSU-scheduled Pallas)
    sched_bucket: int = 1           # WSU trip bucketing (schedule backend)
    prune: Optional[pruning.PruneConfig] = None
    downsample: DownsampleConfig = dataclasses.field(
        default_factory=lambda: DownsampleConfig(enabled=False)
    )
    keyframe: KeyframePolicy = dataclasses.field(default_factory=KeyframePolicy)
    map_window: int = 4             # recent keyframes optimized jointly per
                                    # mapping iteration (one batched render)
    densify_per_kf: int = 384
    seed_stride: int = 3            # initial map seeding grid stride
    seed_opacity: float = 0.7
    fused: bool = True              # scan-fused engine vs per-iteration loop
    sparse_opt: bool = False        # sparse stable/unstable mapping: freeze
                                    # stable Gaussians out of the Adam step,
                                    # the fragment build and the WSU
                                    # schedule (requires prune; False is the
                                    # dense bitwise oracle)
    map_rebuild_stride: int = 6     # mapping fragment-list rebuild cadence
    scan_unroll: int = 4            # lax.scan unroll (XLA:CPU runs rolled
                                    # loop bodies ~30% slower; unrolling
                                    # trades compile time for straight-line
                                    # code while keeping ONE dispatch)
    paged: Optional[pagedmap.PagedConfig] = None
                                    # PagedMap: spatially-bucketed storage +
                                    # frustum-culled working-set views so
                                    # per-frame fragment/schedule cost tracks
                                    # the VISIBLE map, not the whole pool
                                    # (requires fused=True; None is the flat
                                    # bitwise oracle)


@dataclasses.dataclass
class SLAMResult:
    est_w2c: List[np.ndarray]
    gt_w2c: List[np.ndarray]
    keyframe_psnr: List[float]
    ate: float
    work: WorkCounters
    alive_per_frame: List[int]
    wall_time_s: float
    prune_removed: int
    dispatches: int = 0             # jitted calls issued
    syncs: int = 0                  # device->host fetches issued

    @property
    def mean_psnr(self) -> float:
        return float(np.mean(self.keyframe_psnr)) if self.keyframe_psnr else 0.0


@functools.partial(jax.profiler.annotate_function, name="slam.seed_map")
def _seed_map(dataset: SLAMDataset, cfg: SLAMConfig) -> G.GaussianField:
    """Bootstrap the map from frame 0's RGB-D (standard 3DGS-SLAM init)."""
    f0 = dataset.frames[0]
    intr = dataset.intrinsics
    ys = np.arange(0, intr.height, cfg.seed_stride)
    xs = np.arange(0, intr.width, cfg.seed_stride)
    vv, uu = np.meshgrid(ys, xs, indexing="ij")
    uu, vv = uu.reshape(-1), vv.reshape(-1)
    d = f0.depth[vv, uu]
    ok = d > 1e-3
    uu, vv, d = uu[ok], vv[ok], d[ok]
    x_cam = np.stack(
        [(uu + 0.5 - intr.cx) / intr.fx * d, (vv + 0.5 - intr.cy) / intr.fy * d, d], -1
    )
    c2w = np.linalg.inv(f0.w2c_gt)
    pts = x_cam @ c2w[:3, :3].T + c2w[:3, 3]
    cols = f0.rgb[vv, uu]
    n = min(len(pts), cfg.capacity // 2)
    mean_scale = float(np.median(d)) / intr.fx * cfg.seed_stride
    return G.from_points(
        jnp.asarray(pts[:n]), jnp.asarray(np.clip(cols[:n], 0.02, 0.98)),
        capacity=cfg.capacity, scale=mean_scale, opacity=cfg.seed_opacity,
    )


# ---------------------------------------------------------------------------
# the session pytree
# ---------------------------------------------------------------------------


class SessionMeta:
    """Static (aux-data) half of a session: config + intrinsics, hashed and
    compared through ``static_fingerprint`` so sessions built from equal
    configs share one treedef (stackable) and one step-executable cache
    entry."""

    __slots__ = ("cfg", "intr", "_key")

    def __init__(self, cfg: SLAMConfig, intr: Intrinsics):
        self.cfg = cfg
        self.intr = intr
        self._key = ("SlamSession", intr, static_fingerprint(cfg))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, SessionMeta) and self._key == other._key

    def __repr__(self):
        return f"SessionMeta({self.cfg.base_algo}, {self.intr.width}x{self.intr.height})"


class Observation(NamedTuple):
    """One frame's observations (device).  Leaves gain a leading S axis for
    :func:`step_many`."""

    rgb: jnp.ndarray     # (H, W, 3) float32
    depth: jnp.ndarray   # (H, W) float32, 0 = invalid


class StepResult(NamedTuple):
    """Per-frame outputs of a session step (device values — fetch at will).
    Leaves gain a leading S axis under :func:`step_many`."""

    pose: jnp.ndarray          # (4, 4) estimated w2c after tracking
    is_kf: jnp.ndarray         # () bool — this frame became a keyframe
    psnr: jnp.ndarray          # () f32 — post-mapping PSNR (NaN if not kf)
    alive: jnp.ndarray         # () i32 — alive Gaussians after the frame
    work: DeviceWork           # this frame's work snapshot
    track_losses: jnp.ndarray  # (iters_track,)
    fired: jnp.ndarray         # (iters_track,) bool §4.1 boundary iterations
    map_losses: jnp.ndarray    # (iters_map,) (zeros if not kf)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SlamSession:
    """One SLAM stream's complete dynamic state as a registered pytree.

    Every field below ``meta`` is a dynamic leaf (or sub-pytree): stacking N
    sessions along a leading axis (``stack_sessions``) yields a valid
    N-session pytree for :func:`step_many`.  The invariant new code must
    keep: **session state goes in a pytree leaf; static knobs go in
    SLAMConfig** (which keys the compile cache via ``static_fingerprint``).
    """

    meta: SessionMeta                  # static aux data (cfg + intrinsics)
    g: G.GaussianField                 # the map
    map_opt: AdamState                 # mapping Adam moments
    pstate: Optional[pruning.PruneState]  # §4.1 state (None when prune off)
    masked: jnp.ndarray                # (N,) bool mask (prune-off path)
    pose: jnp.ndarray                  # (4, 4) current estimated w2c
    velocity: jnp.ndarray              # (4, 4) constant-velocity model
    traj: jnp.ndarray                  # (F, 4, 4) estimated trajectory
    frame_idx: jnp.ndarray             # () i32 frames processed so far
    kf_rgb: jnp.ndarray                # (W, H, Wd, 3) keyframe ring, oldest
    kf_depth: jnp.ndarray              # (W, H, Wd)      first, fixed shape
    kf_w2c: jnp.ndarray                # (W, 4, 4)
    kf_count: jnp.ndarray              # () i32 populated ring slots (<= W)
    kf_total: jnp.ndarray              # () i32 total keyframes ever
    last_kf_idx: jnp.ndarray           # () i32 frame index of last keyframe
    last_kf_rgb: jnp.ndarray           # (H, Wd, 3) for the photoslam policy
    prev_rgb: jnp.ndarray              # (H, Wd, 3) previous frame (photoslam
    prev_depth: jnp.ndarray            # (H, Wd)     geometric tracking)
    kf_psnr: jnp.ndarray               # (F,) f32 per-keyframe PSNR log (NaN pad)
    alive_log: jnp.ndarray             # (F,) i32 alive Gaussians per frame
    work: "metrics.WideWork"           # cumulative on-device work counters
                                       # (hi/lo int32 carry split, ~2^61
                                       # range — see metrics.WideWork;
                                       # StepResult.work is the per-frame
                                       # int32 snapshot)
    frags: FragmentLists               # cached stage-1 lists @ last keyframe
    sched: Optional[object]            # carried TileSchedule (WSU backend)
    rng: jnp.ndarray                   # densify PRNG key
    tile_baselines: dict               # {num_tiles: (T,) i32} parked §4.1
                                       # churn baselines across §4.2 factor
                                       # switches (empty unless prune +
                                       # downsample; keys fixed at init so
                                       # the treedef never changes)
    page: Optional[pagedmap.PageTable] = None
                                       # PagedMap spatial index over g's rows
                                       # (None when cfg.paged is None); in
                                       # paged mode map_opt's row leaves are
                                       # VIEW-shaped (M = V*C rows)

    _DYN = ("g", "map_opt", "pstate", "masked", "pose", "velocity", "traj",
            "frame_idx", "kf_rgb", "kf_depth", "kf_w2c", "kf_count",
            "kf_total", "last_kf_idx", "last_kf_rgb", "prev_rgb",
            "prev_depth", "kf_psnr", "alive_log", "work", "frags", "sched",
            "rng", "tile_baselines", "page")

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self._DYN), self.meta

    @classmethod
    def tree_unflatten(cls, meta, children):
        return cls(meta, *children)

    # -- conveniences ------------------------------------------------------

    @property
    def cur_masked(self) -> jnp.ndarray:
        return self.pstate.masked if self.pstate is not None else self.masked

    @property
    def batch(self) -> Optional[int]:
        """Leading stacked-session axis length, or None for a solo session."""
        return None if self.frame_idx.ndim == 0 else int(self.frame_idx.shape[0])

    @property
    def max_frames(self) -> int:
        return int(self.traj.shape[-3])

    def replace(self, **kw) -> "SlamSession":
        return dataclasses.replace(self, **kw)


def stack_sessions(sessions: Sequence[SlamSession]) -> SlamSession:
    """Stack solo sessions along a new leading axis for :func:`step_many`.
    All sessions must share one ``SessionMeta`` (equal static config)."""
    metas = {s.meta for s in sessions}
    if len(metas) != 1:
        raise ValueError("stack_sessions needs sessions with identical "
                         "static config (SessionMeta); got "
                         f"{len(metas)} distinct metas")
    if any(s.batch is not None for s in sessions):
        raise ValueError("stack_sessions takes solo sessions, not stacks")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *sessions)


def session_row(stacked: SlamSession, i: int) -> SlamSession:
    """Extract row ``i`` of a stacked session as a solo session."""
    return jax.tree.map(lambda x: x[i], stacked)


def _tree_stack(rows):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


# ---------------------------------------------------------------------------
# step-executable cache (static key — dynamic session leaves never enter it)
# ---------------------------------------------------------------------------

_STEP_CACHE: dict = {}
_BOOT_CACHE: dict = {}
_AUX_JIT_CACHE: dict = {}
_ENGINE_CACHE: dict = {}


def session_step_key(meta_or_session, factor: int = 1,
                     batch: Optional[int] = None):
    """The compile-cache key of a session step: intrinsics + downsample
    factor + stacked-batch size + the config's ``static_fingerprint``.
    Dynamic session leaves are, by construction, not part of it."""
    meta = (meta_or_session.meta if isinstance(meta_or_session, SlamSession)
            else meta_or_session)
    if batch is None and isinstance(meta_or_session, SlamSession):
        batch = meta_or_session.batch
    return ("session-step", meta.intr, factor, batch,
            static_fingerprint(meta.cfg))


def _as_obs(frame) -> Observation:
    """Coerce a dataset Frame / (rgb, depth) pair / Observation to device."""
    if isinstance(frame, Observation):
        rgb, depth = frame.rgb, frame.depth
    elif hasattr(frame, "rgb") and hasattr(frame, "depth"):
        rgb, depth = frame.rgb, frame.depth
    else:
        rgb, depth = frame
    return Observation(rgb=jnp.asarray(rgb, jnp.float32),
                       depth=jnp.asarray(depth, jnp.float32))


# ---------------------------------------------------------------------------
# on-device densification (the host _densify of the legacy runner, traced)
# ---------------------------------------------------------------------------


@scoped("densify")
def _densify_core(g: G.GaussianField, rgb, depth, rendered, w2c,
                  intr: Intrinsics, cfg: SLAMConfig, key):
    """Add Gaussians where the current render misses observed geometry.

    Same selection rule as the legacy host densifier (error-ranked top-2P,
    random P of those, backproject), expressed in jnp so it can ride inside
    the fused step dispatch.  The randomness comes from the session's
    carried PRNG key (folded with the frame index), not host NumPy.

    Returns ``(g, dropped)``: ``G.insert`` fills dead slots lowest-index
    first and silently discards newcomers once none remain, so ``dropped``
    (the () i32 shortfall) surfaces that admission failure through
    ``DeviceWork.densify_dropped``.  In paged mode ``g`` is the working-set
    view whose nursery pages supply the dead rows — page spill drives this
    to zero where a same-capacity flat pool overflows."""
    per = cfg.densify_per_kf
    err = jnp.abs(rendered - rgb).mean(-1)               # (H, W)
    score = jnp.where(depth > 1e-3, err, 0.0).reshape(-1)
    cand = jnp.argsort(-score)[: per * 2]
    sel = jax.random.permutation(key, cand)[:per]
    vv, uu = jnp.unravel_index(sel, err.shape)
    d = depth[vv, uu]
    ok = d > 1e-3
    x_cam = jnp.stack(
        [(uu + 0.5 - intr.cx) / intr.fx * d,
         (vv + 0.5 - intr.cy) / intr.fy * d, d], -1)
    c2w = jnp.linalg.inv(w2c)
    pts = x_cam @ c2w[:3, :3].T + c2w[:3, 3]
    cols = jnp.clip(rgb[vv, uu], 0.02, 0.98)
    # Median depth of the valid picks sets the new Gaussians' scale; with no
    # valid picks the NaN never escapes (no alive rows to insert).
    scale = jnp.nanmedian(jnp.where(ok, d, jnp.nan)) / intr.fx * 2.0
    inv_sig = jnp.log(cols / (1.0 - cols))
    logit_op = float(np.log(0.6 / 0.4))
    new = G.GaussianField(
        mu=pts.astype(jnp.float32),
        log_scale=jnp.broadcast_to(jnp.log(scale), (per, 3)).astype(jnp.float32),
        quat=jnp.tile(jnp.array([1.0, 0.0, 0.0, 0.0], jnp.float32), (per, 1)),
        logit_o=jnp.full((per,), logit_op, jnp.float32),
        color=inv_sig.astype(jnp.float32),
        alive=ok,
    )
    n_new = jnp.sum(new.alive.astype(jnp.int32))
    n_dead = jnp.sum((~g.alive).astype(jnp.int32))
    dropped = jnp.maximum(jnp.minimum(n_new, per) - n_dead, 0)
    return G.insert(g, new, max_new=per), dropped


def _push_ring(buf: jnp.ndarray, row: jnp.ndarray, count) -> jnp.ndarray:
    """Append ``row`` to a fixed-shape oldest-first ring: write slot
    ``count`` while filling, shift-left once full."""
    w = buf.shape[0]
    appended = jax.lax.dynamic_update_index_in_dim(
        buf, row, jnp.minimum(count, w - 1), 0)
    shifted = jnp.concatenate([buf[1:], row[None]], axis=0)
    return jnp.where(count >= w, shifted, appended)


# ---------------------------------------------------------------------------
# the fused step core (one trace per (cfg, factor); one dispatch per frame)
# ---------------------------------------------------------------------------


def _make_row_step(meta: SessionMeta, factor: int):
    """Build the pure per-session step function.  Solo `session_step` jits
    it directly; `step_many` unrolls it per stacked row inside one jit, so
    the per-row computation is the identical trace either way (the bitwise
    anchor of multi-session serving)."""
    cfg, intr = meta.cfg, meta.intr
    st_t = get_stage(intr, cfg, factor)     # tracking stage (may be scaled)
    st_1 = get_stage(intr, cfg, 1)          # mapping/eval stage
    kp = cfg.keyframe
    paged = cfg.paged
    geo_scan = (get_geo_scan(intr, cfg)[0]
                if cfg.base_algo == "photoslam" else None)

    def row_step(sess: SlamSession, rgb: jnp.ndarray, depth: jnp.ndarray):
        g = sess.g
        pstate = sess.pstate
        masked = pstate.masked if pstate is not None else sess.masked
        idx = sess.frame_idx
        d_since = idx - sess.last_kf_idx

        # -- pre-tracking keyframe decision (gsslam re-decides after) ------
        with jax.named_scope("slam.keyframe"):
            if kp.kind == "monogs":
                pre_kf = d_since >= kp.interval
            elif kp.kind == "splatam":
                pre_kf = jnp.asarray(True)
            elif kp.kind == "photoslam":
                err = jnp.sqrt(jnp.mean((rgb - sess.last_kf_rgb) ** 2))
                pre_kf = err > kp.pho_thresh
            else:                               # gsslam: post-tracking only
                pre_kf = jnp.asarray(False)

        base = sess.velocity @ sess.pose

        # -- PagedMap working-set gather (inside this same dispatch) -------
        # Pages visible from the predicted camera or ANY keyframe-ring pose
        # (mapping renders the whole ring) form the frame's working set; a
        # page outside every frustum contributes zero fragments and zero
        # grads (projection culls its rows), so running the step on the
        # gathered view is exact up to the static visible_pages cap.  When
        # every page is selected the gather is the ascending identity and
        # the step is bitwise-equal to the flat path.
        page = sess.page
        view_idx = None
        if paged is not None:
            with jax.named_scope("slam.paged"):
                cams = jnp.concatenate([base[None], sess.kf_w2c], axis=0)
                vis = pagedmap.pages_visible(page, intr, cams,
                                             margin=paged.margin)
                selected = pagedmap.select_pages(
                    vis, page.occupancy, paged.visible_pages,
                    priority=pagedmap.page_distances(page, base))
                view_idx = pagedmap.view_rows(page.row2page, selected,
                                              paged.page_capacity)
                g_store, pstate_store = g, pstate
                g = pagedmap.gather_field(g, view_idx)
                if pstate is not None:
                    pstate = pruning.gather_rows(pstate, view_idx)
                    masked = pstate.masked
                else:
                    masked = masked[view_idx]

        # -- tracking: the PR 1/2 scan bundles as pure functions ----------
        with jax.named_scope("slam.track"):
            obs_rgb = downsample_image(rgb, factor)
            obs_depth = downsample_depth(depth, factor)
            work0 = device_work_zero()
            k_track = cfg.iters_track
            if cfg.base_algo == "photoslam":
                pts_w, cols, _, valid = geometric.backproject_grid(
                    sess.prev_rgb, sess.prev_depth, sess.pose, intr, stride=4)
                xi = geo_scan(base, pts_w, cols, valid, rgb, depth)
                track_px = (intr.height // 4) * (intr.width // 4)
                zero = jnp.asarray(0, jnp.int32)
                work_t = DeviceWork(
                    fragments=zero,
                    pixels=jnp.asarray(track_px * k_track, jnp.int32),
                    gaussians_iters=zero,
                    iterations=jnp.asarray(k_track, jnp.int32),
                    unstable_gaussians=zero, sched_programs=zero,
                    skipped_fragments=zero, densify_dropped=zero,
                    frag_build_rows=zero)
                track_losses = jnp.zeros((k_track,), jnp.float32)
                fired = jnp.zeros((k_track,), bool)
            else:
                frags = st_t._build_core(g, masked, base)
                if pstate is not None:
                    xi, g, pstate, work_t, track_losses, fired = \
                        st_t._track_scan_prune(g, pstate, base, obs_rgb,
                                               obs_depth, frags, work0)
                    masked = pstate.masked
                else:
                    xi, work_t, track_losses, fired = \
                        st_t._track_scan_noprune(g, masked, base, obs_rgb,
                                                 obs_depth, frags, work0)

            new_pose = lie.se3_exp(xi) @ base
        velocity = new_pose @ jnp.linalg.inv(sess.pose)
        traj = sess.traj.at[idx].set(new_pose)

        with jax.named_scope("slam.keyframe"):
            if kp.kind == "gsslam":
                last_kf_pose = jax.lax.dynamic_index_in_dim(
                    sess.kf_w2c, sess.kf_count - 1, 0, keepdims=False)
                rel = lie.se3_log(new_pose @ lie.se3_inverse(last_kf_pose))
                is_kf = ((jnp.linalg.norm(rel[:3]) > kp.trans_thresh)
                         | (jnp.linalg.norm(rel[3:]) > kp.rot_thresh))
            else:
                is_kf = pre_kf

        # -- mapping (keyframes only) under lax.cond ----------------------
        key = jax.random.fold_in(sess.rng, idx)
        w_slots = cfg.map_window
        # Sparse stable/unstable mapping: the stability bit maintained by
        # the tracking scan above freezes stable Gaussians through the
        # mapping dispatch.  PruneState rides the cond operand only in
        # sparse mode so the dense trace stays the pre-sparse oracle.
        sparse = bool(getattr(cfg, "sparse_opt", False))

        def map_branch(op):
            if sparse:
                (g, map_opt, pstate_b, kf_rgb, kf_depth, kf_w2c, kf_count,
                 kf_total, kf_psnr_buf, frags_l, sched_l) = op
            else:
                (g, map_opt, kf_rgb, kf_depth, kf_w2c, kf_count, kf_total,
                 kf_psnr_buf, frags_l, sched_l) = op
                pstate_b = None
            # Eval render at the tracked pose drives densification.
            out = render(silence(g, masked), Camera(intr, new_pose),
                         st_1.plan)
            g2, dropped = _densify_core(g, rgb, depth, out.image, new_pose,
                                        intr, cfg, key)
            stable = None
            if sparse:
                # Newcomers land in previously-dead slots whose stale
                # EMA/age could freeze them at birth — reset those rows.
                pstate_b = pruning.mark_born(pstate_b, g2.alive & ~g.alive)
                stable = pstate_b.stable
            g = g2
            opt0 = Adam(lr=cfg.lr_map).init(G.params_of(g))
            kf_rgb = _push_ring(kf_rgb, rgb, kf_count)
            kf_depth = _push_ring(kf_depth, depth, kf_count)
            kf_w2c = _push_ring(kf_w2c, new_pose, kf_count)
            n2 = jnp.minimum(kf_count + 1, w_slots)
            kf_valid = jnp.arange(w_slots) < n2
            g, map_opt, work_m, map_losses, image = st_1._map_scan_masked(
                g, masked, opt0, kf_w2c, kf_rgb, kf_depth, kf_valid, work0,
                stable)
            # The densify-eval render above and the serving-cache refresh
            # below each build one fragment list over g's rows.
            work_m = work_m._replace(
                densify_dropped=work_m.densify_dropped + dropped,
                frag_build_rows=work_m.frag_build_rows
                + jnp.asarray(2 * g.mu.shape[0], jnp.int32))
            psnr_v = psnr_dev(image, rgb)
            kf_psnr_buf = kf_psnr_buf.at[kf_total].set(psnr_v)
            # Refresh the cached stage-1 fragment lists (+ WSU schedule) of
            # the current map at the new keyframe pose — the session's
            # serving cache for external renders (always dense: external
            # renders see the whole map).  In paged mode the build runs over
            # the working-set view; the cached indices are remapped to
            # storage rows so external consumers render against sess.g.
            frags_l = st_1._build_core(g, masked, new_pose)
            if paged is not None:
                frags_l = remap_fragment_rows(frags_l, view_idx)
            sched_l = (build_schedule(frags_l.count, st_1.plan.chunk,
                                      bucket=cfg.sched_bucket,
                                      max_trips=st_1.plan.max_trips)
                       if st_1.scheduled else sched_l)
            ret = (g, map_opt, kf_rgb, kf_depth, kf_w2c, n2, kf_total + 1,
                   kf_psnr_buf, frags_l, sched_l, work_m, map_losses,
                   psnr_v)
            return ret + (pstate_b,) if sparse else ret

        def skip_branch(op):
            if sparse:
                (g, map_opt, pstate_b, kf_rgb, kf_depth, kf_w2c, kf_count,
                 kf_total, kf_psnr_buf, frags_l, sched_l) = op
            else:
                (g, map_opt, kf_rgb, kf_depth, kf_w2c, kf_count, kf_total,
                 kf_psnr_buf, frags_l, sched_l) = op
                pstate_b = None
            ret = (g, map_opt, kf_rgb, kf_depth, kf_w2c, kf_count, kf_total,
                   kf_psnr_buf, frags_l, sched_l, device_work_zero(),
                   jnp.zeros((cfg.iters_map,), jnp.float32),
                   jnp.asarray(jnp.nan, jnp.float32))
            return ret + (pstate_b,) if sparse else ret

        operand = ((g, sess.map_opt, pstate, sess.kf_rgb, sess.kf_depth,
                    sess.kf_w2c, sess.kf_count, sess.kf_total, sess.kf_psnr,
                    sess.frags, sess.sched) if sparse else
                   (g, sess.map_opt, sess.kf_rgb, sess.kf_depth, sess.kf_w2c,
                    sess.kf_count, sess.kf_total, sess.kf_psnr, sess.frags,
                    sess.sched))
        with jax.named_scope("slam.map"):
            cond_out = jax.lax.cond(is_kf, map_branch, skip_branch, operand)
        if sparse:
            pstate = cond_out[-1]
            cond_out = cond_out[:-1]
        (g, map_opt, kf_rgb, kf_depth, kf_w2c, kf_count, kf_total,
         kf_psnr_buf, frags_l, sched_l, work_m, map_losses, psnr_v) = cond_out

        # -- PagedMap scatter-back + keyframe page-table rebuild -----------
        if paged is not None:
            with jax.named_scope("slam.paged"):
                g = pagedmap.scatter_field(g_store, g, view_idx)
                if pstate is not None:
                    pstate = pruning.scatter_rows(pstate_store, pstate,
                                                  view_idx)
                # Rebuild the spatial index on keyframes (the only step
                # that admits rows): densified newcomers migrate from
                # nursery pages to their Morton bucket and dead rows
                # re-collect page-locally.  Between keyframes the stale
                # table is conservative — pruning removals only shrink true
                # AABBs/occupancy, never grow them.
                page = jax.lax.cond(
                    is_kf,
                    lambda gg: pagedmap.build_page_table(gg, paged),
                    lambda gg: page,
                    g)

        alive_now = g.num_alive()
        step_work = device_work_merge(work_t, work_m)
        new_sess = sess.replace(
            g=g, map_opt=map_opt, pstate=pstate, pose=new_pose,
            velocity=velocity, traj=traj, frame_idx=idx + 1,
            kf_rgb=kf_rgb, kf_depth=kf_depth, kf_w2c=kf_w2c,
            kf_count=kf_count, kf_total=kf_total,
            last_kf_idx=jnp.where(is_kf, idx, sess.last_kf_idx),
            last_kf_rgb=jnp.where(is_kf, rgb, sess.last_kf_rgb),
            prev_rgb=rgb, prev_depth=depth,
            kf_psnr=kf_psnr_buf,
            alive_log=sess.alive_log.at[idx].set(alive_now),
            work=wide_work_add(sess.work, step_work),
            frags=frags_l, sched=sched_l, page=page,
        )
        result = StepResult(pose=new_pose, is_kf=is_kf, psnr=psnr_v,
                            alive=alive_now, work=step_work,
                            track_losses=track_losses, fired=fired,
                            map_losses=map_losses)
        return new_sess, result

    return row_step


def make_many_step(meta: SessionMeta, batch: int, factor: int = 1):
    """The pure (un-jitted) S-row step function ``many(stacked, obs) ->
    (stacked', StepResult)``: the solo row trace unrolled once per stacked
    row.  :func:`step_many` jits it directly; the SlamServe tier
    (:mod:`repro.slam.server`) jits the SAME function under device
    shardings — both paths share this builder so per-row computation stays
    the identical trace (the bitwise anchor of multi-session serving).
    ``factor`` must match the cache key it is compiled under (serving
    always uses 1 — see :func:`require_servable`)."""
    row_step = _make_row_step(meta, factor)

    def many(stacked, obs: Observation):
        rows = [row_step(session_row(stacked, s), obs.rgb[s],
                         obs.depth[s]) for s in range(batch)]
        return (_tree_stack([r[0] for r in rows]),
                _tree_stack([r[1] for r in rows]))

    return many


def _step_fn(meta: SessionMeta, factor: int, batch: Optional[int]):
    key = session_step_key(meta, factor, batch)
    if key not in _STEP_CACHE:
        if batch is None:
            row_step = _make_row_step(meta, factor)

            def solo(sess, obs: Observation):
                return row_step(sess, obs.rgb, obs.depth)
            _STEP_CACHE[key] = f32_jit(solo, donate_argnames="sess")
        else:
            _STEP_CACHE[key] = f32_jit(make_many_step(meta, batch, factor),
                                       donate_argnames="stacked")
    return _STEP_CACHE[key]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.profiler.annotate_function, name="slam.session_init")
def session_init(dataset: SLAMDataset, cfg: SLAMConfig, *,
                 max_frames: Optional[int] = None, seed: int = 0,
                 stats: Optional[EngineStats] = None) -> SlamSession:
    """Seed the map from frame 0 and bootstrap its mapping (one dispatch).
    The returned session has consumed frame 0; feed frames 1.. to
    :func:`session_step`."""
    intr = dataset.intrinsics
    if cfg.downsample.enabled:
        assert intr.height % 64 == 0 and intr.width % 64 == 0, (
            "dynamic downsampling needs 64-divisible frames (16px tiles at "
            f"the 4x stage); got {intr.height}x{intr.width}")
    if cfg.paged is not None:
        if not cfg.fused:
            raise ValueError("SLAMConfig.paged requires cfg.fused=True: the "
                             "frustum cull + working-set gather ride inside "
                             "the fused step dispatch")
        pagedmap.validate_paged(cfg.paged, cfg.capacity)
    meta = SessionMeta(cfg, intr)
    st_1 = get_stage(intr, cfg, 1)
    f0 = dataset.frames[0]
    num_f = int(max_frames or dataset.num_frames)
    w = cfg.map_window
    h, wd = intr.height, intr.width

    g = _seed_map(dataset, cfg)
    pstate = (pruning.init_state(g, st_1.grid.num_tiles, cfg.prune)
              if cfg.prune else None)
    # Pre-seed one parked-baseline slot per §4.2 grid (the -1 sentinel =
    # "no comparable baseline") so factor switches swap churn history
    # in-place and the session treedef never changes shape.
    tile_baselines: dict = {}
    if cfg.prune and cfg.downsample.enabled:
        for f in (1, 2, 4):
            t = get_stage(intr, cfg, f).grid.num_tiles
            tile_baselines[t] = jnp.full((t,), -1, jnp.int32)
    pose0 = jnp.asarray(f0.w2c_gt, jnp.float32)
    rgb0 = jnp.asarray(f0.rgb, jnp.float32)
    depth0 = jnp.asarray(f0.depth, jnp.float32)
    masked = jnp.zeros((cfg.capacity,), bool)
    kf_rgb = jnp.zeros((w, h, wd, 3), jnp.float32).at[0].set(rgb0)
    kf_depth = jnp.zeros((w, h, wd), jnp.float32).at[0].set(depth0)
    kf_w2c = jnp.tile(pose0[None], (w, 1, 1))
    kf_valid = jnp.arange(w) < 1

    boot = _boot_fn(meta)
    if stats is not None:
        stats.dispatches += 1
    map_opt0 = Adam(lr=cfg.lr_map).init(G.params_of(g))
    with jax.profiler.TraceAnnotation("slam.boot"):
        g, map_opt, work_m, psnr0, alive0, frags_l, sched_l = boot(
            g, masked if pstate is None else pstate.masked, map_opt0,
            kf_w2c, kf_rgb, kf_depth, kf_valid)

    # PagedMap: the bootstrap mapped the full pool (frame 0 sees the whole
    # seed map); build the initial spatial index and park the Adam moments
    # at the frame-0 working-set view shape — every subsequent keyframe
    # re-inits them anyway, so only the (M, ...) row shape is load-bearing.
    page = None
    if cfg.paged is not None:
        pc = cfg.paged
        page = pagedmap.build_page_table(g, pc)
        cams = jnp.concatenate([pose0[None], kf_w2c], axis=0)
        vis = pagedmap.pages_visible(page, intr, cams, margin=pc.margin)
        selected = pagedmap.select_pages(
            vis, page.occupancy, pc.visible_pages,
            priority=pagedmap.page_distances(page, pose0))
        view_idx = pagedmap.view_rows(page.row2page, selected,
                                      pc.page_capacity)
        map_opt = optim.gather_rows(map_opt, view_idx)

    return SlamSession(
        meta=meta, g=g, map_opt=map_opt, pstate=pstate, masked=masked,
        pose=pose0, velocity=jnp.eye(4, dtype=jnp.float32),
        traj=jnp.zeros((num_f, 4, 4), jnp.float32).at[0].set(pose0),
        frame_idx=jnp.asarray(1, jnp.int32),
        kf_rgb=kf_rgb, kf_depth=kf_depth, kf_w2c=kf_w2c,
        kf_count=jnp.asarray(1, jnp.int32), kf_total=jnp.asarray(1, jnp.int32),
        # Every leaf owns its buffer: the step donates the whole session,
        # and one buffer donated twice is refused.
        last_kf_idx=jnp.asarray(0, jnp.int32), last_kf_rgb=jnp.array(rgb0),
        prev_rgb=rgb0, prev_depth=depth0,
        kf_psnr=jnp.full((num_f,), jnp.nan, jnp.float32).at[0].set(psnr0),
        alive_log=jnp.zeros((num_f,), jnp.int32).at[0].set(alive0),
        work=work_m, frags=frags_l, sched=sched_l,
        rng=jax.random.PRNGKey(seed),
        tile_baselines=tile_baselines,
        page=page,
    )


def _boot_fn(meta: SessionMeta):
    key = ("session-boot", meta._key)
    if key not in _BOOT_CACHE:
        cfg, intr = meta.cfg, meta.intr
        st_1 = get_stage(intr, cfg, 1)

        def boot(g, masked, map_opt0, kf_w2c, kf_rgb, kf_depth, kf_valid):
            g, opt, work_m, _, image = st_1._map_scan_masked(
                g, masked, map_opt0, kf_w2c, kf_rgb, kf_depth, kf_valid,
                device_work_zero())
            # The serving-cache build below sweeps the pool once more.
            work_m = work_m._replace(
                frag_build_rows=work_m.frag_build_rows
                + jnp.asarray(g.mu.shape[0], jnp.int32))
            work_m = wide_work_add(wide_work_zero(), work_m)
            psnr0 = psnr_dev(image, kf_rgb[0])
            frags_l = st_1._build_core(g, masked, kf_w2c[0])
            sched_l = (build_schedule(frags_l.count, st_1.plan.chunk,
                                      bucket=cfg.sched_bucket,
                                      max_trips=st_1.plan.max_trips)
                       if st_1.scheduled else None)
            return g, opt, work_m, psnr0, g.num_alive(), frags_l, sched_l

        _BOOT_CACHE[key] = f32_jit(boot)
    return _BOOT_CACHE[key]


@functools.partial(jax.profiler.annotate_function, name="slam.step")
def session_step(session: SlamSession, frame, *, factor: int = 1,
                 stats: Optional[EngineStats] = None
                 ) -> Tuple[SlamSession, StepResult]:
    """Advance one solo session by one frame.

    With ``cfg.fused=True`` (default) this is ONE jitted dispatch covering
    fragment build, the tracking scan, the keyframe decision, densification,
    the masked-window mapping scan and the PSNR eval.  ``cfg.fused=False``
    runs the per-iteration baseline (the dispatch-per-iteration oracle).
    ``factor`` is the §4.2 downsampling side factor for this frame's
    tracking (host-chosen; one executable per factor)."""
    if session.batch is not None:
        raise ValueError("session_step takes a solo session; use step_many "
                         "for stacked sessions")
    meta = session.meta
    obs = _as_obs(frame)
    session = _maybe_retile(session, factor)
    if not meta.cfg.fused:
        return _step_unfused(session, obs, factor, stats)
    fn = _step_fn(meta, factor, None)
    if stats is not None:
        stats.dispatches += 1
    return fn(session, obs)


def require_servable(cfg: SLAMConfig, what: str = "step_many") -> None:
    """Validate that a config can serve stacked multi-session steps:
    ``fused=True`` and downsampling off (the §4.2 side factor is a
    host-static per-dispatch choice a shared dispatch cannot make per
    session).  Shared by :func:`step_many` and the SlamServe tier."""
    if not cfg.fused:
        raise ValueError(f"{what} requires cfg.fused=True")
    if cfg.downsample.enabled:
        raise ValueError(f"{what} requires downsampling disabled (the "
                         "side factor is a per-dispatch static)")


def stack_observations(frames, batch: int) -> Observation:
    """Coerce S per-session frames (or an already-stacked ``Observation``)
    to one ``Observation`` with leading S axes."""
    if isinstance(frames, Observation):
        return frames
    rows = [_as_obs(f) for f in frames]
    if len(rows) != batch:
        raise ValueError(f"expected {batch} frames, got {len(rows)}")
    return Observation(rgb=jnp.stack([r.rgb for r in rows]),
                       depth=jnp.stack([r.depth for r in rows]))


def step_many(stacked: SlamSession, frames, *,
              stats: Optional[EngineStats] = None
              ) -> Tuple[SlamSession, StepResult]:
    """Advance S stacked sessions by one frame each — ONE shared executable,
    ONE dispatch.  ``frames`` is a sequence of S per-session frames (or an
    ``Observation`` with leading S axes).  Per-session keyframe/pruning
    divergence runs under each row's ``lax.cond`` boundaries; per-row
    results are bitwise-equal to solo :func:`session_step` runs.

    Serving constraints (:func:`require_servable`): ``cfg.fused=True`` and
    downsampling disabled."""
    s = stacked.batch
    if s is None:
        raise ValueError("step_many takes a stacked session "
                         "(see stack_sessions)")
    meta = stacked.meta
    require_servable(meta.cfg)
    obs = stack_observations(frames, s)
    fn = _step_fn(meta, 1, s)
    if stats is not None:
        stats.dispatches += 1
    return fn(stacked, obs)


def session_finalize(session: SlamSession, gt_w2c=None, *,
                     wall_time_s: float = 0.0,
                     stats: Optional[EngineStats] = None) -> SLAMResult:
    """Fetch the session's device-resident logs (ONE sync) and assemble the
    legacy :class:`SLAMResult`."""
    if session.batch is not None:
        raise ValueError("session_finalize takes a solo session; index a "
                         "stack with session_row first")
    removed = (session.pstate.removed if session.pstate is not None
               else jnp.asarray(0, jnp.int32))
    (traj, n, kf_psnr, kf_total, alive_log, work, removed) = jax.device_get(
        (session.traj, session.frame_idx, session.kf_psnr, session.kf_total,
         session.alive_log, session.work, removed))
    if stats is not None:
        stats.syncs += 1
    n = int(n)
    est = [np.asarray(traj[i]) for i in range(n)]
    gt = list(gt_w2c) if gt_w2c is not None else []
    # A partially-run session (e.g. a pool retiree) aligns against the
    # ground truth of the frames it actually processed.
    ate = ate_rmse(est, gt[:n]) if len(gt) >= n and n >= 2 else float("nan")
    counters = WorkCounters(frames=n, **wide_work_totals(work))
    return SLAMResult(
        est_w2c=est,
        gt_w2c=gt,
        keyframe_psnr=[float(x) for x in kf_psnr[: int(kf_total)]],
        ate=ate,
        work=counters,
        alive_per_frame=[int(x) for x in alive_log[:n]],
        wall_time_s=wall_time_s,
        prune_removed=int(removed),
        dispatches=stats.dispatches if stats is not None else 0,
        syncs=stats.syncs if stats is not None else 0,
    )


def run_sequence(dataset: SLAMDataset, cfg: SLAMConfig,
                 verbose: bool = False, telemetry=None) -> SLAMResult:
    """Run a whole dataset through the session API (the non-deprecated
    successor of ``run_slam``): init, one :func:`session_step` per frame,
    finalize.  Per-frame host syncs happen only when the host actually
    needs a device value (downsampling's factor schedule, verbose prints).

    ``telemetry`` (a :class:`repro.obs.Telemetry`) records per-frame spans
    and a ``frame_latency_ms`` histogram (host step wall — the dispatch is
    async) labeled ``stream=dataset.name``, and folds the finalized work
    counters into the registry.  It rides values this loop already holds —
    no extra fetch, no extra dispatch; a telemetry-on run is
    bitwise-identical to a telemetry-off run (tests/test_obs.py)."""
    tele = telemetry_or_off(telemetry)
    run_sw = Stopwatch()
    stats = EngineStats()
    stream = dataset.name
    with tele.span("init", stream=stream):
        sess = session_init(dataset, cfg, stats=stats)
    last_kf_idx = 0                      # host mirror for the §4.2 schedule
    need_iskf = cfg.downsample.enabled
    kp = cfg.keyframe

    for idx in range(1, dataset.num_frames):
        frame = dataset.frames[idx]
        d_since = idx - last_kf_idx
        pre_kf = False
        if cfg.downsample.enabled and kp.kind in ("monogs", "splatam"):
            pre_kf = (kp.kind == "splatam") or d_since >= kp.interval
        elif cfg.downsample.enabled and kp.kind == "photoslam":
            # photoslam's pre-decision only needs host frame data
            last_rgb = dataset.frames[last_kf_idx].rgb
            pre_kf = float(np.sqrt(np.mean((frame.rgb - last_rgb) ** 2))) \
                > kp.pho_thresh
        factor = side_factor(d_since, pre_kf, cfg.downsample)
        sw = Stopwatch()
        with tele.span("frame", stream=stream, idx=idx):
            sess, res = session_step(sess, frame, factor=factor, stats=stats)
        tele.latency("frame_latency_ms", sw.elapsed() * 1e3, stream=stream)
        if need_iskf or verbose:
            # The host needs is_kf anyway — telemetry rides the SAME fetch.
            is_kf = bool(jax.device_get(res.is_kf))
            stats.syncs += 1
            if is_kf:
                last_kf_idx = idx
                tele.count("keyframes", stream=stream)
            if verbose and idx % 10 == 0:
                alive, psnr_buf, total = jax.device_get(
                    (res.alive, sess.kf_psnr, sess.kf_total))
                print(f"[{cfg.base_algo}] frame {idx}: kf={is_kf} "
                      f"factor={factor} alive={int(alive)} "
                      f"psnr={float(psnr_buf[int(total) - 1]):.2f}")

    result = session_finalize(
        sess, gt_w2c=[f.w2c_gt for f in dataset.frames], stats=stats)
    # Read after finalize's fetch: on an async device the loop above only
    # enqueued the steps.
    result.wall_time_s = run_sw.elapsed()
    tele.result(stream, result)
    return result


# ---------------------------------------------------------------------------
# host-side shape adaptation (downsample factor switches under pruning)
# ---------------------------------------------------------------------------


def _maybe_retile(session: SlamSession, factor: int) -> SlamSession:
    """§4.2 factor switches change the tracking grid, so the carried
    ``PruneState.prev_tile_count`` must be re-shaped before the dispatch
    (the fused step core is shape-polymorphic via retrace, not rank-
    polymorphic).  Displaced baselines park in the session's own
    ``tile_baselines`` leaves — per-stream state stays in the pytree, so
    concurrent sessions with equal configs can never clobber each other's
    churn history."""
    if session.pstate is None:
        return session
    st = get_stage(session.meta.intr, session.meta.cfg, factor)
    if session.pstate.prev_tile_count.shape[0] == st.grid.num_tiles:
        return session
    baselines = dict(session.tile_baselines)  # retile_state mutates it
    pstate = pruning.retile_state(session.pstate, st.grid.num_tiles,
                                  baselines)
    return session.replace(pstate=pstate, tile_baselines=baselines)


# ---------------------------------------------------------------------------
# the per-iteration baseline (cfg.fused=False): same algorithm, the seed's
# dispatch shape — kept as the parity oracle and benchmark baseline
# ---------------------------------------------------------------------------


def _engine_for(meta: SessionMeta) -> StepEngine:
    if meta not in _ENGINE_CACHE:
        _ENGINE_CACHE[meta] = StepEngine(meta.intr, meta.cfg)
    return _ENGINE_CACHE[meta]


def _densify_jit(meta: SessionMeta):
    key = ("densify", meta._key)
    if key not in _AUX_JIT_CACHE:
        cfg, intr = meta.cfg, meta.intr

        def fn(g, rgb, depth, rendered, w2c, k):
            return _densify_core(g, rgb, depth, rendered, w2c, intr, cfg, k)

        _AUX_JIT_CACHE[key] = f32_jit(fn)
    return _AUX_JIT_CACHE[key]


def _step_unfused(sess: SlamSession, obs: Observation, factor: int,
                  stats: Optional[EngineStats]
                  ) -> Tuple[SlamSession, StepResult]:
    """The dispatch-per-iteration session step: same algorithm as the fused
    core (device densify, device keyframe policy, masked-window mapping),
    executed as the legacy loop shape — per-iteration dispatches and
    per-iteration host syncs.  Oracle for tests, baseline for benchmarks."""
    meta = sess.meta
    cfg, intr = meta.cfg, meta.intr
    kp = cfg.keyframe
    stats = stats if stats is not None else EngineStats()
    eng = _engine_for(meta)
    eng.stats = stats
    st_1 = eng.stage(1)
    rgb, depth = obs.rgb, obs.depth

    idx, kf_count, kf_total, last_kf_idx = (int(x) for x in jax.device_get(
        (sess.frame_idx, sess.kf_count, sess.kf_total, sess.last_kf_idx)))
    stats.syncs += 1
    d_since = idx - last_kf_idx

    if kp.kind == "monogs":
        pre_kf = d_since >= kp.interval
    elif kp.kind == "splatam":
        pre_kf = True
    elif kp.kind == "photoslam":
        stats.syncs += 1
        pre_kf = float(jax.device_get(
            jnp.sqrt(jnp.mean((rgb - sess.last_kf_rgb) ** 2)))) > kp.pho_thresh
    else:
        pre_kf = False

    g, pstate = sess.g, sess.pstate
    masked = pstate.masked if pstate is not None else sess.masked
    base = sess.velocity @ sess.pose
    obs_rgb = downsample_image(rgb, factor)
    obs_depth = downsample_depth(depth, factor)

    if cfg.base_algo == "photoslam":
        pts_w, cols, _, valid = geometric.backproject_grid(
            sess.prev_rgb, sess.prev_depth, sess.pose, intr, stride=4)
        xi, work_t = eng.geo_track_frame(base, pts_w, cols, valid, rgb, depth)
        k = cfg.iters_track
        track_losses = jnp.zeros((k,), jnp.float32)
        fired = jnp.zeros((k,), bool)
    else:
        tres = eng.track_frame(factor, g, pstate, masked, base, obs_rgb,
                               obs_depth)
        xi, g, pstate, work_t = tres.xi, tres.g, tres.pstate, tres.work
        track_losses = jnp.asarray(tres.losses)
        fired = jnp.asarray(tres.fired)
        if pstate is not None:
            masked = pstate.masked

    new_pose = lie.se3_exp(xi) @ base
    velocity = new_pose @ jnp.linalg.inv(sess.pose)
    traj = sess.traj.at[idx].set(new_pose)

    if kp.kind == "gsslam":
        last_kf_pose = sess.kf_w2c[kf_count - 1]
        rel = lie.se3_log(new_pose @ lie.se3_inverse(last_kf_pose))
        tn, rn = jax.device_get((jnp.linalg.norm(rel[:3]),
                                 jnp.linalg.norm(rel[3:])))
        stats.syncs += 1
        is_kf = float(tn) > kp.trans_thresh or float(rn) > kp.rot_thresh
    else:
        is_kf = bool(pre_kf)

    map_opt = sess.map_opt
    kf_rgb, kf_depth, kf_w2c = sess.kf_rgb, sess.kf_depth, sess.kf_w2c
    kf_psnr_buf, frags_l, sched_l = sess.kf_psnr, sess.frags, sess.sched
    work_m = device_work_zero()
    map_losses = jnp.zeros((cfg.iters_map,), jnp.float32)
    psnr_v = jnp.asarray(jnp.nan, jnp.float32)

    if is_kf:
        rendered = eng.render_eval(g, masked, new_pose)
        key = jax.random.fold_in(sess.rng, idx)
        g2, dropped = _densify_jit(meta)(g, rgb, depth, rendered, new_pose,
                                         key)
        stats.dispatches += 1
        stable = None
        if getattr(cfg, "sparse_opt", False):
            # Mirror the fused map_branch: reset stability state of
            # densified newcomers, then freeze the stable set.
            pstate = pruning.mark_born(pstate, g2.alive & ~g.alive)
            stable = pstate.stable
        g = g2
        keep = None if stable is None else ~stable
        map_opt = Adam(lr=cfg.lr_map).init(G.params_of(g))
        kcount = jnp.asarray(kf_count, jnp.int32)
        kf_rgb = _push_ring(kf_rgb, rgb, kcount)
        kf_depth = _push_ring(kf_depth, depth, kcount)
        kf_w2c = _push_ring(kf_w2c, new_pose, kcount)
        n2 = min(kf_count + 1, cfg.map_window)
        kf_valid = jnp.arange(cfg.map_window) < n2

        def build_slot(pose):
            if keep is None:
                return eng._call(st_1.build, g, masked, pose), 0
            frs, sk = eng._call(st_1.build_sparse, g, masked, keep, pose)
            stats.syncs += 1
            return frs, int(sk)

        # Per-iteration mapping over the masked ring (dispatch + sync per
        # iteration — the baseline's cost shape).  Invalid cache rows only
        # need to be finite: duplicate slot 0's build.
        built = [build_slot(kf_w2c[i]) for i in range(n2)]
        cache_rows = [b[0] for b in built]
        skipped = [b[1] for b in built]
        cache_rows += [cache_rows[0]] * (cfg.map_window - n2)
        totals = [int(c.total) for c in cache_rows[:n2]]
        progs = [int(st_1.slot_programs(c)) for c in cache_rows[:n2]]
        stats.syncs += 2 * n2
        stacked = stack_fragment_lists(cache_rows)
        fr = px = gi = it_n = un = pr = sk_n = 0
        stable_bg = None
        if keep is not None:
            # One stable-background render for the whole phase (stable
            # rows are bit-frozen), composited under every iteration's
            # unstable render and accounted once over the valid slots —
            # the fused _map_scan_masked convention.
            stable_bg, bg_total, bg_progs = eng._call(
                st_1.stable_bg, g, masked, stable, kf_w2c)
            stats.syncs += 2
            fr += int(jnp.sum(bg_total[:n2]))
            pr += int(jnp.sum(bg_progs[:n2]))
        losses = []
        for it in range(cfg.iters_map):
            loss, g, map_opt = eng._call(
                st_1.map_iter, g, masked, map_opt, kf_w2c, kf_rgb, kf_depth,
                stacked, None, kf_valid=kf_valid, unstable=keep,
                stable_bg=stable_bg)
            stats.syncs += 1
            n_alive = int(g.num_alive())
            n_opt = (n_alive if stable is None
                     else int(jnp.sum(g.alive & ~stable)))
            fr += sum(totals)
            px += n2 * st_1.pixels
            gi += n2 * n_alive
            un += n2 * n_opt
            pr += sum(progs)
            sk_n += sum(skipped)
            it_n += 1
            losses.append(loss)
            if (it + 1) % cfg.map_rebuild_stride == 0:
                slot = ((it + 1) // cfg.map_rebuild_stride - 1) % n2
                fresh, skipped[slot] = build_slot(kf_w2c[slot])
                totals[slot] = int(fresh.total)
                progs[slot] = int(st_1.slot_programs(fresh))
                stats.syncs += 2
                stacked = update_fragment_slot(
                    stacked, jnp.asarray(slot, jnp.int32), fresh)
        # Mirror the fused accounting bitwise: n2 window builds + the static
        # stride rebuilds + 3 single-list sweeps (densify-eval render, final
        # eval render, serving-cache refresh), each over the full pool.
        work_m = DeviceWork(fragments=fr, pixels=px, gaussians_iters=gi,
                            iterations=it_n, unstable_gaussians=un,
                            sched_programs=pr, skipped_fragments=sk_n,
                            densify_dropped=dropped,
                            frag_build_rows=(n2 + cfg.iters_map
                                             // cfg.map_rebuild_stride + 3)
                            * cfg.capacity)
        map_losses = jnp.stack(losses)
        image = eng.render_eval(g, masked, kf_w2c[n2 - 1])
        psnr_v = psnr_dev(image, rgb)
        kf_psnr_buf = kf_psnr_buf.at[kf_total].set(psnr_v)
        frags_l = eng._call(st_1.build, g, masked, new_pose)
        if st_1.scheduled:
            sched_l = build_schedule(frags_l.count, st_1.plan.chunk,
                                     bucket=cfg.sched_bucket,
                                     max_trips=st_1.plan.max_trips)
        kf_count, kf_total = n2, kf_total + 1

    alive_now = g.num_alive()
    step_work = device_work_merge(work_t, work_m)
    new_sess = sess.replace(
        g=g, map_opt=map_opt, pstate=pstate, pose=new_pose,
        velocity=velocity, traj=traj,
        frame_idx=jnp.asarray(idx + 1, jnp.int32),
        kf_rgb=kf_rgb, kf_depth=kf_depth, kf_w2c=kf_w2c,
        kf_count=jnp.asarray(kf_count, jnp.int32),
        kf_total=jnp.asarray(kf_total, jnp.int32),
        last_kf_idx=jnp.asarray(idx if is_kf else last_kf_idx, jnp.int32),
        last_kf_rgb=rgb if is_kf else sess.last_kf_rgb,
        prev_rgb=rgb, prev_depth=depth,
        kf_psnr=kf_psnr_buf,
        alive_log=sess.alive_log.at[idx].set(alive_now),
        work=wide_work_add(sess.work, step_work),
        frags=frags_l, sched=sched_l,
    )
    result = StepResult(pose=new_pose, is_kf=jnp.asarray(is_kf),
                        psnr=psnr_v, alive=alive_now, work=step_work,
                        track_losses=track_losses, fired=fired,
                        map_losses=map_losses)
    return new_sess, result


# ---------------------------------------------------------------------------
# the serving pool
# ---------------------------------------------------------------------------


def validate_admission(new_session: SlamSession, stacked: SlamSession) -> None:
    """Shared admission preconditions for pool row swaps
    (:class:`SessionPool` and the SlamServe ``ShardedPool``): equal static
    config, solo shape, matching trajectory capacity.  New preconditions
    go here so both serving tiers enforce them."""
    if new_session.meta != stacked.meta:
        raise ValueError("admitted session's static config differs from "
                         "the pool's")
    if new_session.batch is not None:
        raise ValueError("admit a solo session, not a stack")
    if new_session.max_frames != stacked.max_frames:
        raise ValueError(
            "admitted session's max_frames "
            f"({new_session.max_frames}) must match the pool's "
            f"({stacked.max_frames}); pass max_frames= to "
            "session_init")


class SessionPool:
    """Host wrapper serving S concurrent SLAM streams through one stacked
    session pytree: every :meth:`step` is ONE dispatch of ONE shared
    executable; :meth:`swap` admits/retires a sequence by replacing a
    pytree row (other rows' computation is untouched — rows are bitwise
    independent)."""

    def __init__(self, sessions: Sequence[SlamSession]):
        self._stacked = stack_sessions(list(sessions))
        self.stats = EngineStats()

    @property
    def size(self) -> int:
        return self._stacked.batch

    @property
    def stacked(self) -> SlamSession:
        return self._stacked

    def session(self, slot: int) -> SlamSession:
        return session_row(self._stacked, slot)

    def step(self, frames) -> StepResult:
        """Advance every slot by one frame (one dispatch).  Returns the
        stacked :class:`StepResult` (device; index rows lazily)."""
        self._stacked, res = step_many(self._stacked, frames,
                                       stats=self.stats)
        return res

    def swap(self, slot: int, new_session: SlamSession) -> SlamSession:
        """Retire the session in ``slot`` (returned as a solo session) and
        admit ``new_session`` in its place."""
        validate_admission(new_session, self._stacked)
        old = self.session(slot)
        self._stacked = jax.tree.map(
            lambda buf, row: buf.at[slot].set(row), self._stacked,
            new_session)
        return old

    def finalize(self, slot: int, gt_w2c=None, **kw) -> SLAMResult:
        return session_finalize(self.session(slot), gt_w2c=gt_w2c,
                                stats=self.stats, **kw)
