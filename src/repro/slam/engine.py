"""Fused on-device SLAM step engine (the RTGS frame loop's inner loops).

The paper's thesis is that 3DGS-SLAM wastes most of its time on redundancy
*between* pipeline stages; the host-level analogue is a frame loop that
re-enters the accelerator once per optimization iteration and syncs scalars
back after every step.  This module removes that redundancy: the K tracking
iterations and the mapping-window iterations each run as a **single
``jax.lax.scan`` dispatch**, carrying

  (pose delta xi / map params, Adam state, §4.1 ``PruneState``,
   cached ``FragmentLists``, int32 ``DeviceWork`` counters)

through the scan.  Pruning interval boundaries fire under ``lax.cond``
(`pruning.cond_interval_update`), fragment lists are rebuilt *inside* the
scan on boundaries/strides (Obs. 6 reuse), and work counters stay device
resident — fetched once per frame, not per iteration.

Mapping optimizes the **whole keyframe window jointly**: every iteration
renders all window views as ONE batched multi-view dispatch (RasterAPI v2
stacked-grid batching, bit-identical to a per-view loop) and steps Adam on
the mean window loss; the post-mapping eval render rides inside the same
scan dispatch.

Layering:

  host (runner.py)      keyframe policy, densify/seed, constant velocity —
                        decisions GPU systems also make on CPU
  engine (this file)    per-(stage, phase) jitted step bundles; one dispatch
                        per tracking phase / mapping phase
  core/*                rendering, sorting, pruning primitives

Both a **fused** path (scan bundles) and an **unfused** per-iteration path
(the seed's loop shape: one dispatch + 2-3 host syncs per iteration) are
provided behind the same API; the unfused path exists as the before/after
baseline for benchmarks and as the parity oracle for tests.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gaussians as G
from repro.core import lie, pruning
from repro.core.camera import Camera, Intrinsics
from repro.core.lie import f32_jit
from repro.core.losses import slam_loss
from repro.core.raster_api import RasterPlan, static_fingerprint
from repro.core.render import render
from repro.core.schedule import (
    scheduled_trips,
    tile_trips,
    build_schedule,
)
from repro.core.sorting import (
    FragmentLists,
    build_fragment_lists,
    count_skipped_fragments,
    make_tile_grid,
    stack_fragment_lists,
    update_fragment_slot,
)
from repro.core.projection import project
from repro.slam import geometric
from repro.slam.metrics import DeviceWork, device_work_add, device_work_zero
from repro.train.optimizer import (
    Adam,
    AdamState,
    apply_updates,
    apply_updates_masked,
)


def silence(g: G.GaussianField, masked: jnp.ndarray) -> G.GaussianField:
    """Mask-pruned or dead Gaussians render as nothing (cached fragment
    lists may still reference them until the next rebuild)."""
    off = masked | (~g.alive)
    return g._replace(logit_o=jnp.where(off, -30.0, g.logit_o))


@dataclasses.dataclass
class EngineStats:
    """Host-observable pipeline overhead the fused engine removes."""

    dispatches: int = 0   # jitted-callable invocations issued
    syncs: int = 0        # device->host fetches issued

    def record(self, telemetry, **labels) -> None:
        """Export the running totals into a SlamScope registry (host ints
        only — no fetch, no dispatch).  ``telemetry`` may be ``None`` or a
        disabled sink; the frame-step/admin split lives at the server layer,
        so engine dispatches count as ``kind="step"``."""
        if telemetry is None or not getattr(telemetry, "enabled", False):
            return
        telemetry.count("dispatches", self.dispatches, kind="step", **labels)
        telemetry.count("syncs", self.syncs, **labels)


@dataclasses.dataclass
class TrackResult:
    xi: jnp.ndarray                       # (6,) optimized pose delta (device)
    g: G.GaussianField                    # field after §4.1 removals
    pstate: Optional[pruning.PruneState]
    work: DeviceWork                      # per-phase snapshot (device or ints)
    losses: jnp.ndarray                   # (K,)
    fired: np.ndarray | jnp.ndarray       # (K,) bool — boundary iterations


@dataclasses.dataclass
class MapResult:
    g: G.GaussianField
    opt_state: AdamState
    work: DeviceWork
    losses: jnp.ndarray
    builds: int = 0
    image: Optional[jnp.ndarray] = None   # fresh render of the current
                                          # keyframe after mapping (device)


def _pose_adam_zero() -> AdamState:
    return AdamState(step=jnp.zeros((), jnp.int32), mu=jnp.zeros(6), nu=jnp.zeros(6))


def _stage_key(intr: Intrinsics, cfg, factor: int):
    """Everything a _Stage's compiled bundles depend on.  Stages are cached
    module-wide on this key so repeated ``run_slam`` calls (serving many
    trajectories) reuse XLA executables instead of re-jitting per engine.

    The key is **derived automatically** from the static leaves of the whole
    config (``raster_api.static_fingerprint``, which also covers the
    :class:`RasterPlan` each stage builds from it) — a new cfg field can
    never be forgotten here, so the cache can never serve stale executables
    (tests/test_engine.py::test_stage_key_distinguishes_engine_fields)."""
    return (intr, factor, static_fingerprint(cfg))


_STAGE_CACHE: dict = {}
_GEO_CACHE: dict = {}
_GEO_JIT_CACHE: dict = {}


def get_stage(intr: Intrinsics, cfg, factor: int) -> "_Stage":
    """Module-wide stage lookup (compiled-bundle cache keyed on
    :func:`_stage_key`).  Shared by :class:`StepEngine` and the
    :mod:`repro.slam.session` step cores, so an engine and a session with
    the same static config reuse the same XLA executables."""
    key = _stage_key(intr, cfg, factor)
    if key not in _STAGE_CACHE:
        _STAGE_CACHE[key] = _Stage(intr, cfg, factor)
    return _STAGE_CACHE[key]


def get_geo_scan(intr: Intrinsics, cfg):
    """Pure geometric-tracking cores for the Photo-SLAM base algorithm:
    ``(geo_scan, geo_vg)`` where ``geo_scan(base, pts, cols, valid, rgb,
    depth) -> xi`` runs the K pose iterations as one ``lax.scan`` (traceable
    inside larger bundles — the session step embeds it) and ``geo_vg`` is the
    per-iteration value-and-grad (the unfused baseline)."""
    key = (intr, cfg.lr_pose, cfg.iters_track)
    if key not in _GEO_CACHE:
        geo_vg = geometric.make_geometric_tracker(intr)
        iters = cfg.iters_track
        popt = Adam(lr=cfg.lr_pose * 2)

        def geo_scan(base, pts, cs, vl, im, dp):
            def body(carry, _):
                xi, ostate = carry
                _, gxi = geo_vg(xi, base, pts, cs, vl, im, dp)
                upd, ostate = popt.update(gxi, ostate)
                return (xi + upd, ostate), None

            (xi, _), _ = jax.lax.scan(
                body, (jnp.zeros(6), popt.init(jnp.zeros(6))), None,
                length=iters)
            return xi

        _GEO_CACHE[key] = (geo_scan, geo_vg)
    return _GEO_CACHE[key]


class _Stage:
    """Per-downsample-factor step bundles.  Jitted callables are created
    eagerly (compilation is lazy — a bundle that never runs never compiles).
    """

    def __init__(self, intr: Intrinsics, cfg, factor: int):
        self.factor = factor
        self.intr = intr.scaled(factor)
        self.grid = make_tile_grid(self.intr.height, self.intr.width)
        self.plan = RasterPlan(grid=self.grid, backend=cfg.backend,
                               capacity=cfg.frag_capacity,
                               sched_bucket=cfg.sched_bucket)
        # WSU: carry an execution schedule through the scans next to the
        # cached fragment lists (rebuilt only on the same boundaries).
        self.scheduled = cfg.backend == "schedule"
        self.pixels = self.intr.height * self.intr.width
        self.cfg = cfg
        # Sparse stable/unstable optimization (ROADMAP item 3): mapping
        # freezes stable Gaussians out of the Adam step, the fragment build
        # and the WSU schedule.  Consumption-only flag — the stability bit
        # itself is maintained in PruneState whenever pruning is on.
        self.sparse = bool(getattr(cfg, "sparse_opt", False))
        if self.sparse and cfg.prune is None:
            raise ValueError("sparse_opt=True requires cfg.prune (the "
                             "stability bit rides PruneState)")

        self.build = f32_jit(self._build_core)
        self.build_sparse = f32_jit(self._sparse_build_core)
        self.slot_programs = f32_jit(self._slot_programs_core)
        self.track_iter = f32_jit(self._track_iter_core)
        self.map_iter = f32_jit(self._map_iter_core)
        self.stable_bg = f32_jit(self._stable_bg_core)
        self.render_eval = f32_jit(self._render_eval_core)
        self.track_scan_noprune = f32_jit(self._track_scan_noprune)
        if cfg.prune is not None:
            self.track_scan_prune = f32_jit(
                self._track_scan_prune, donate_argnames=("g", "pstate", "work"))
        donate_map = ("g", "opt_state", "work")
        self.map_scan = f32_jit(self._map_scan, donate_argnames=donate_map)
        self.map_scan_masked = f32_jit(self._map_scan_masked,
                                       donate_argnames=donate_map)

    # ---- cores (pure, shared by fused scans and per-iteration jits) -----

    def _build_core(self, g, masked, w2c, keep=None) -> FragmentLists:
        proj = project(silence(g, masked), Camera(self.intr, w2c))
        return build_fragment_lists(proj, self.grid, self.cfg.frag_capacity,
                                    keep=keep)

    def _sparse_build_core(self, g, masked, keep, w2c):
        """Stability-masked fragment build: stable Gaussians emit no
        fragments, so stable-only tiles get zero counts (and thus zero-trip
        WSU programs downstream).  Also returns the () int32 count of
        fragments the mask dropped vs the dense build."""
        proj = project(silence(g, masked), Camera(self.intr, w2c))
        frags = build_fragment_lists(proj, self.grid, self.cfg.frag_capacity,
                                     keep=keep)
        return frags, count_skipped_fragments(proj, self.grid, keep)

    def _sched_core(self, frags: FragmentLists):
        """WSU schedule from the cached fragment counts (pure device math;
        rebuilt only where ``frags`` is rebuilt)."""
        return build_schedule(frags.count, self.plan.chunk,
                              bucket=self.cfg.sched_bucket,
                              max_trips=self.plan.max_trips)

    def _slot_programs_core(self, frags: FragmentLists, sched=None):
        """() int32 scheduled raster programs for one view, in the WSU's
        subtile-streaming unit: total chunk trips (``schedule.
        scheduled_trips`` on the WSU backend, the per-tile capacity-loop
        equivalent otherwise).  This is the quantity the sparse build
        shrinks — a stable-only tile streams zero trips, and the total
        tracks streamed work (pair granularity would hide sparsity: pairing
        folds empty tiles onto loaded ones)."""
        if self.scheduled:
            if sched is None:
                sched = self._sched_core(frags)
            return scheduled_trips(sched)
        return tile_trips(frags.count, self.plan.chunk)

    def _track_iter_core(self, g, masked, xi, ostate, base_w2c, obs_rgb,
                         obs_depth, frags, sched=None):
        """One tracking iteration: render → Eq. 6 loss → pose Adam step.
        Returns the per-Gaussian param grads too (§4.1 reuses them)."""
        g_eff = silence(g, masked)

        def loss_fn(xi_, params):
            gg = G.with_params(g_eff, params)
            cam = Camera(self.intr, lie.se3_exp(xi_) @ base_w2c)
            out = render(gg, cam, self.plan.with_sched(sched), frags=frags)
            return slam_loss(out.image, out.depth, out.alpha, obs_rgb,
                             obs_depth, self.cfg.lambda_pho)

        params = G.params_of(g_eff)
        loss, (g_xi, g_params) = jax.value_and_grad(loss_fn, argnums=(0, 1))(xi, params)
        opt = Adam(lr=self.cfg.lr_pose)
        upd, ostate = opt.update(g_xi, ostate)
        return loss, xi + upd, ostate, g_params

    def _map_iter_core(self, g, masked, opt_state, kf_w2c, kf_rgb, kf_depth,
                       cache, scheds=None, kf_valid=None, unstable=None,
                       stable_bg=None):
        """One mapping iteration over the **whole keyframe window**: one
        batched multi-view render (leading window axis on ``kf_*`` and the
        stacked ``cache``), mean window loss, one Adam step.  With a
        one-keyframe window this is exactly the old single-view iteration.

        ``kf_valid`` (a (W,) bool mask) supports the session layer's
        fixed-shape keyframe ring: invalid slots still render (static
        shapes) but contribute exactly zero to the loss, so a mask with V
        valid slots equals a V-length window bitwise (``x * 1.0 == x`` and
        ``x + 0.0 == x``).

        ``unstable`` (an (N,) bool row mask) switches the Adam step to the
        sparse stable/unstable form: stable rows get zero updates, keep
        their moments, and their params are returned through a ``where``
        select so they stay **bit-frozen**.  All-True mask == dense step
        bitwise (the oracle).

        ``stable_bg`` (RTG-SLAM-style stable background, sparse_opt mode)
        is the per-slot ``(image, depth, final_t)`` of the **stable-only**
        render: the sparse caches hold unstable fragments only, so the raw
        render is missing the frozen map and the loss would drag unstable
        Gaussians into duplicating it.  Compositing the unstable render
        over the frozen background (``c_u + T_u * c_s``, ``T_u * T_s``)
        restores the full image at zero per-iteration cost — the stable
        rows are bit-frozen, so the background is a constant for the whole
        mapping phase (rendered once by the caller, no gradient flows).
        With an empty stable set the background is ``(0, 0, 1)`` and every
        composite reduces bitwise to the dense expressions (``x + T*0`` on
        values that are never ``-0.0``, ``1 - T*1.0``), preserving the
        all-unstable oracle."""
        g_eff = silence(g, masked)
        w_len = kf_w2c.shape[0]

        def loss_fn(params):
            gg = G.with_params(g_eff, params)
            out = render(gg, Camera(self.intr, kf_w2c),
                         self.plan.with_sched(scheds), frags=cache)
            if stable_bg is None:
                img, dep, alp = out.image, out.depth, out.alpha
            else:
                bg_img, bg_dep, bg_t = stable_bg
                t = out.final_t
                img = out.image + t[..., None] * bg_img
                dep = out.depth + t * bg_dep
                alp = 1.0 - t * bg_t
            per_view = [
                slam_loss(img[b], dep[b], alp[b],
                          kf_rgb[b], kf_depth[b], self.cfg.lambda_pho)
                for b in range(w_len)
            ]
            if kf_valid is None:
                return sum(per_view) / w_len
            vw = kf_valid.astype(jnp.float32)
            return sum(per_view[b] * vw[b] for b in range(w_len)) / jnp.sum(vw)

        params = G.params_of(g)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        opt = Adam(lr=self.cfg.lr_map)
        if unstable is None:
            upd, opt_state = opt.update(grads, opt_state)
            return loss, G.with_params(g, apply_updates(params, upd)), opt_state
        upd, opt_state = opt.update_masked(grads, opt_state, unstable)
        new_params = apply_updates_masked(params, upd, unstable)
        return loss, G.with_params(g, new_params), opt_state

    def _stable_bg_core(self, g, masked, stable, kf_w2c):
        """Render the **stable-only** map for every window slot: the frozen
        background the sparse mapping loss composites the unstable render
        over.  Stable rows are bit-frozen through the whole mapping phase,
        so one render here stays exact for every iteration — the phase's
        only extra cost (and the per-slot totals/trips are returned so the
        caller can account it once, not per iteration).  An empty stable
        set yields ``(0, 0, 1)`` buffers, zero fragments and zero trips:
        the dense/all-unstable oracle is untouched."""
        cache_s, _ = jax.vmap(
            lambda p: self._sparse_build_core(g, masked, stable, p))(kf_w2c)
        scheds_s = jax.vmap(self._sched_core)(cache_s) if self.scheduled else None
        out = render(silence(g, masked), Camera(self.intr, kf_w2c),
                     self.plan.with_sched(scheds_s), frags=cache_s)
        progs_w = (jax.vmap(scheduled_trips)(scheds_s) if self.scheduled
                   else jax.vmap(
                       lambda c: tile_trips(c, self.plan.chunk))(
                           cache_s.count))
        return (out.image, out.depth, out.final_t), cache_s.total, progs_w

    def _render_eval_core(self, g, masked, w2c):
        out = render(silence(g, masked), Camera(self.intr, w2c), self.plan)
        return out.image

    # ---- fused bundles ---------------------------------------------------

    def _track_scan_noprune(self, g, masked, base_w2c, obs_rgb, obs_depth,
                            frags, work):
        # WSU previous-iteration reuse: one schedule for the whole phase
        # (frags is fixed here), computed on device inside this dispatch.
        sched = self._sched_core(frags) if self.scheduled else None
        # The caller-built pre-track fragment lists swept every row of g —
        # in paged mode g is the visible view, so this counter is what the
        # PagedMap bench compares against the flat path's full-map sweeps.
        work = work._replace(frag_build_rows=work.frag_build_rows
                             + jnp.asarray(g.mu.shape[0], jnp.int32))

        def body(carry, _):
            xi, ostate, work = carry
            loss, xi, ostate, _ = self._track_iter_core(
                g, masked, xi, ostate, base_w2c, obs_rgb, obs_depth, frags,
                sched)
            alive_eff = jnp.sum((g.alive & ~masked).astype(jnp.int32))
            # unstable=0: tracking optimizes the pose, not Gaussian params,
            # so it contributes nothing to the optimized-Gaussian counter.
            work = device_work_add(work, frags.total, self.pixels, alive_eff,
                                   unstable=0)
            return (xi, ostate, work), (loss, jnp.asarray(False))

        (xi, _, work), (losses, fired) = jax.lax.scan(
            body, (jnp.zeros(6), _pose_adam_zero(), work), None,
            length=self.cfg.iters_track,
            unroll=min(self.cfg.scan_unroll, self.cfg.iters_track))
        return xi, work, losses, fired

    def _track_scan_prune(self, g, pstate, base_w2c, obs_rgb, obs_depth,
                          frags, work):
        prune_cfg = self.cfg.prune
        sched0 = self._sched_core(frags) if self.scheduled else None
        n_rows = jnp.asarray(g.mu.shape[0], jnp.int32)
        # Pre-track build by the caller, plus one rebuild per fired pruning
        # interval inside the scan body below.
        work = work._replace(frag_build_rows=work.frag_build_rows + n_rows)

        def body(carry, _):
            if self.scheduled:
                xi, ostate, g, pstate, frags, sched, work = carry
            else:
                xi, ostate, g, pstate, frags, work = carry
                sched = None
            loss, xi, ostate, g_params = self._track_iter_core(
                g, pstate.masked, xi, ostate, base_w2c, obs_rgb, obs_depth,
                frags, sched)
            alive_eff = jnp.sum((g.alive & ~pstate.masked).astype(jnp.int32))
            work = device_work_add(work, frags.total, self.pixels, alive_eff,
                                   unstable=0)
            # Stability EMA/age ride the same grads (zero extra backward
            # passes); maintained whenever pruning is on, consumed only
            # when cfg.sparse_opt.
            pstate = pruning.accumulate(pstate, g_params, prune_cfg,
                                        alive=g.alive)

            def build_fn(gg, mm):
                return self._build_core(gg, mm, lie.se3_exp(xi) @ base_w2c)

            pstate, g, frags, fired = pruning.cond_interval_update(
                pstate, g, frags, build_fn, prune_cfg)
            work = work._replace(frag_build_rows=work.frag_build_rows
                                 + jnp.where(fired, n_rows, 0))
            if self.scheduled:
                # Re-schedule exactly when the lists rebuilt (same boundary).
                sched = jax.lax.cond(fired, lambda fr, _s: self._sched_core(fr),
                                     lambda _fr, s: s, frags, sched)
                return (xi, ostate, g, pstate, frags, sched, work), (loss, fired)
            return (xi, ostate, g, pstate, frags, work), (loss, fired)

        if self.scheduled:
            carry0 = (jnp.zeros(6), _pose_adam_zero(), g, pstate, frags,
                      sched0, work)
            (xi, _, g, pstate, frags, _, work), (losses, fired) = jax.lax.scan(
                body, carry0, None, length=self.cfg.iters_track,
                unroll=min(self.cfg.scan_unroll, self.cfg.iters_track))
        else:
            carry0 = (jnp.zeros(6), _pose_adam_zero(), g, pstate, frags, work)
            (xi, _, g, pstate, frags, work), (losses, fired) = jax.lax.scan(
                body, carry0, None, length=self.cfg.iters_track,
                unroll=min(self.cfg.scan_unroll, self.cfg.iters_track))
        return xi, g, pstate, work, losses, fired

    def _map_scan(self, g, masked, opt_state, kf_w2c, kf_rgb, kf_depth, work,
                  stable=None):
        """Whole mapping phase in one dispatch: build the window's fragment
        caches (vmapped), then scan the iterations — each iteration renders
        the **whole keyframe window as one batched stacked-grid dispatch**
        (no per-keyframe cycling) and stride-rebuilds one slot's cache
        round-robin (Obs. 6 reuse).

        ``stable`` (an (N,) bool mask, sparse_opt mode) freezes stable
        Gaussians through all three sparsity layers inside this SAME
        dispatch: masked Adam (``unstable`` row mask), stability-masked
        fragment builds (``keep=~stable``, including stride rebuilds), and
        the WSU schedule built from the masked counts.  The frozen map is
        rendered ONCE as a per-slot stable background
        (:meth:`_stable_bg_core`) and composited under every iteration's
        unstable render, so the loss still targets the full image.  The
        post-mapping eval render stays dense — reported PSNR is always
        full-map PSNR.  ``stable=None`` (or all-False) is the dense
        bitwise oracle.

        The window length is static (one executable per length, cached
        module-wide) so no padded slots are ever built."""
        stride = self.cfg.map_rebuild_stride
        w_len = kf_w2c.shape[0]
        # Row mask is ~stable alone (pruning.optimizable_mask): dead/masked
        # rows are already silenced with exactly-zero grads, and including
        # them keeps the all-unstable case bitwise-equal to the dense path.
        keep = None if stable is None else ~stable
        if keep is None:
            cache = jax.vmap(lambda p: self._build_core(g, masked, p))(kf_w2c)
            skipped_w = jnp.zeros((w_len,), jnp.int32)
            stable_bg = None
        else:
            cache, skipped_w = jax.vmap(
                lambda p: self._sparse_build_core(g, masked, keep, p))(kf_w2c)
            # One stable-background render for the whole phase (stable rows
            # are bit-frozen), accounted once — not per iteration.
            stable_bg, bg_total, bg_progs = self._stable_bg_core(
                g, masked, stable, kf_w2c)
            work = work._replace(
                fragments=work.fragments + jnp.sum(bg_total),
                sched_programs=work.sched_programs + jnp.sum(bg_progs))
        # WSU: one schedule per window slot, carried with the cache and
        # rebuilt on the same stride boundaries.
        scheds = jax.vmap(self._sched_core)(cache) if self.scheduled else None
        # Fragment-build row sweeps this phase: the W window builds, the
        # stride rebuilds (a static count — the cond fires iff
        # (it+1) % stride == 0) and the final eval render's internal build.
        # The one-off sparse stable-background builds are excluded so the
        # all-unstable sparse path stays bitwise-equal to the dense oracle.
        builds = w_len + self.cfg.iters_map // stride + 1
        work = work._replace(
            frag_build_rows=work.frag_build_rows
            + jnp.asarray(builds * g.mu.shape[0], jnp.int32))

        def body(carry, it):
            g, opt_state, cache, scheds, skipped_w, work = carry
            loss, g, opt_state = self._map_iter_core(
                g, masked, opt_state, kf_w2c, kf_rgb, kf_depth, cache, scheds,
                unstable=keep, stable_bg=stable_bg)
            n_opt = jnp.sum((g.alive if stable is None else g.alive & ~stable)
                            .astype(jnp.int32))
            progs_w = (jax.vmap(scheduled_trips)(scheds) if self.scheduled
                       else jax.vmap(
                           lambda c: tile_trips(c, self.plan.chunk))(
                               cache.count))
            work = device_work_add(
                work, jnp.sum(cache.total), w_len * self.pixels,
                w_len * jnp.sum(g.alive.astype(jnp.int32)),
                unstable=w_len * n_opt, programs=jnp.sum(progs_w),
                skipped=jnp.sum(skipped_w))

            def rebuild(operand):
                c, s, sk = operand
                slot = jnp.mod((it + 1) // stride - 1, w_len)  # round-robin
                pose = jax.lax.dynamic_index_in_dim(kf_w2c, slot, 0,
                                                    keepdims=False)
                if keep is None:
                    fresh = self._build_core(g, masked, pose)
                else:
                    fresh, f_sk = self._sparse_build_core(g, masked, keep, pose)
                    sk = jax.lax.dynamic_update_index_in_dim(sk, f_sk, slot,
                                                             axis=0)
                c = update_fragment_slot(c, slot, fresh)
                if self.scheduled:
                    s = update_fragment_slot(s, slot, self._sched_core(fresh))
                return c, s, sk

            cache, scheds, skipped_w = jax.lax.cond(
                jnp.mod(it + 1, stride) == 0, rebuild, lambda o: o,
                (cache, scheds, skipped_w))
            return (g, opt_state, cache, scheds, skipped_w, work), loss

        (g, opt_state, _, _, _, work), losses = jax.lax.scan(
            body, (g, opt_state, cache, scheds, skipped_w, work),
            jnp.arange(self.cfg.iters_map, dtype=jnp.int32),
            unroll=min(self.cfg.scan_unroll, self.cfg.iters_map))
        # Fresh post-mapping render of the current keyframe (window's last
        # slot) inside the same dispatch — the runner's PSNR eval without a
        # separate render_eval dispatch.
        image = self._render_eval_core(g, masked, kf_w2c[-1])
        return g, opt_state, work, losses, image

    def _map_scan_masked(self, g, masked, opt_state, kf_w2c, kf_rgb, kf_depth,
                         kf_valid, work, stable=None):
        """Fixed-shape variant of :meth:`_map_scan` for the session layer's
        keyframe ring: the window always has ``map_window`` slots and a
        (W,) bool ``kf_valid`` mask marks the V populated ones (a contiguous
        prefix, oldest first).  Invalid slots render but are excluded from
        the loss, the work counters, the round-robin stride rebuild and the
        final eval — so a half-full ring matches a V-length window exactly,
        while every window fill shares ONE executable (the property the
        vmapped multi-session step needs).

        ``stable`` enables the sparse stable/unstable path exactly as in
        :meth:`_map_scan` (masked Adam + masked builds + masked schedule);
        invalid slots contribute zero to the sparsity counters too."""
        stride = self.cfg.map_rebuild_stride
        w_len = kf_w2c.shape[0]
        n_valid = jnp.sum(kf_valid.astype(jnp.int32))
        valid_i = kf_valid.astype(jnp.int32)
        keep = None if stable is None else ~stable
        if keep is None:
            cache = jax.vmap(lambda p: self._build_core(g, masked, p))(kf_w2c)
            skipped_w = jnp.zeros((w_len,), jnp.int32)
            stable_bg = None
        else:
            cache, skipped_w = jax.vmap(
                lambda p: self._sparse_build_core(g, masked, keep, p))(kf_w2c)
            # One stable-background render for the whole phase (stable rows
            # are bit-frozen); invalid slots contribute zero to the one-time
            # accounting, matching the per-iteration counters.
            stable_bg, bg_total, bg_progs = self._stable_bg_core(
                g, masked, stable, kf_w2c)
            work = work._replace(
                fragments=work.fragments + jnp.sum(bg_total * valid_i),
                sched_programs=work.sched_programs + jnp.sum(bg_progs * valid_i))
        scheds = jax.vmap(self._sched_core)(cache) if self.scheduled else None
        # Valid-only build accounting (invalid ring slots build padded lists
        # but are excluded, mirroring the other counters): V window builds +
        # static stride rebuilds + the final eval render's internal build.
        work = work._replace(
            frag_build_rows=work.frag_build_rows
            + (n_valid + self.cfg.iters_map // stride + 1)
            * jnp.asarray(g.mu.shape[0], jnp.int32))

        def body(carry, it):
            g, opt_state, cache, scheds, skipped_w, work = carry
            loss, g, opt_state = self._map_iter_core(
                g, masked, opt_state, kf_w2c, kf_rgb, kf_depth, cache, scheds,
                kf_valid=kf_valid, unstable=keep, stable_bg=stable_bg)
            n_opt = jnp.sum((g.alive if stable is None else g.alive & ~stable)
                            .astype(jnp.int32))
            progs_w = (jax.vmap(scheduled_trips)(scheds) if self.scheduled
                       else jax.vmap(
                           lambda c: tile_trips(c, self.plan.chunk))(
                               cache.count))
            work = device_work_add(
                work, jnp.sum(cache.total * valid_i),
                n_valid * self.pixels,
                n_valid * jnp.sum(g.alive.astype(jnp.int32)),
                unstable=n_valid * n_opt,
                programs=jnp.sum(progs_w * valid_i),
                skipped=jnp.sum(skipped_w * valid_i))

            def rebuild(operand):
                c, s, sk = operand
                slot = jnp.mod((it + 1) // stride - 1, n_valid)  # round-robin
                pose = jax.lax.dynamic_index_in_dim(kf_w2c, slot, 0,
                                                    keepdims=False)
                if keep is None:
                    fresh = self._build_core(g, masked, pose)
                else:
                    fresh, f_sk = self._sparse_build_core(g, masked, keep, pose)
                    sk = jax.lax.dynamic_update_index_in_dim(sk, f_sk, slot,
                                                             axis=0)
                c = update_fragment_slot(c, slot, fresh)
                if self.scheduled:
                    s = update_fragment_slot(s, slot, self._sched_core(fresh))
                return c, s, sk

            cache, scheds, skipped_w = jax.lax.cond(
                jnp.mod(it + 1, stride) == 0, rebuild, lambda o: o,
                (cache, scheds, skipped_w))
            return (g, opt_state, cache, scheds, skipped_w, work), loss

        (g, opt_state, _, _, _, work), losses = jax.lax.scan(
            body, (g, opt_state, cache, scheds, skipped_w, work),
            jnp.arange(self.cfg.iters_map, dtype=jnp.int32),
            unroll=min(self.cfg.scan_unroll, self.cfg.iters_map))
        # Eval render of the newest populated slot (the current keyframe).
        pose = jax.lax.dynamic_index_in_dim(kf_w2c, n_valid - 1, 0,
                                            keepdims=False)
        image = self._render_eval_core(g, masked, pose)
        return g, opt_state, work, losses, image


class StepEngine:
    """The on-device optimization engine behind ``run_slam``.

    Host code hands a frame's observations to ``track_frame`` /
    ``map_frame`` and gets back device-resident results; with
    ``cfg.fused=True`` (default) each phase is one scan dispatch, with
    ``fused=False`` the seed's per-iteration loop runs instead (baseline
    for benchmarks/tests).
    """

    def __init__(self, intr: Intrinsics, cfg):
        self.intr = intr
        self.cfg = cfg
        self.stats = EngineStats()
        self._geo = None
        self._geo_vg = None
        # Per-grid churn baselines parked across downsample-factor switches
        # (see pruning.retile_state).
        self._tile_baselines: dict = {}

    # ---- bookkeeping -----------------------------------------------------

    def _call(self, fn, *args, **kw):
        self.stats.dispatches += 1
        return fn(*args, **kw)

    def fetch(self, tree):
        """Device→host sync, counted.  Use once per frame, not per iteration."""
        self.stats.syncs += 1
        return jax.device_get(tree)

    def stage(self, factor: int) -> _Stage:
        return get_stage(self.intr, self.cfg, factor)

    # ---- phases ----------------------------------------------------------

    def render_eval(self, g, masked, w2c, factor: int = 1):
        return self._call(self.stage(factor).render_eval, g, masked, jnp.asarray(w2c))

    def build_lists(self, g, masked, w2c, factor: int = 1) -> FragmentLists:
        return self._call(self.stage(factor).build, g, masked, jnp.asarray(w2c))

    def track_frame(self, factor: int, g, pstate, masked, base_w2c, obs_rgb,
                    obs_depth) -> TrackResult:
        """Run the K tracking iterations for one frame.  ``pstate=None``
        disables §4.1; otherwise ``masked`` is ignored in favor of
        ``pstate.masked``."""
        st = self.stage(factor)
        base = jnp.asarray(base_w2c)
        if pstate is not None:
            pstate = pruning.retile_state(pstate, st.grid.num_tiles,
                                          self._tile_baselines)
            masked = pstate.masked
        frags = self._call(st.build, g, masked, base)
        if self.cfg.fused:
            return self._track_fused(st, g, pstate, masked, base, obs_rgb,
                                     obs_depth, frags)
        return self._track_unfused(st, g, pstate, masked, base, obs_rgb,
                                   obs_depth, frags)

    def _track_fused(self, st, g, pstate, masked, base, obs_rgb, obs_depth, frags):
        work = device_work_zero()
        if pstate is None:
            xi, work, losses, fired = self._call(
                st.track_scan_noprune, g, masked, base, obs_rgb, obs_depth,
                frags, work)
            return TrackResult(xi=xi, g=g, pstate=None, work=work,
                               losses=losses, fired=fired)
        xi, g, pstate, work, losses, fired = self._call(
            st.track_scan_prune, g, pstate, base, obs_rgb, obs_depth, frags, work)
        return TrackResult(xi=xi, g=g, pstate=pstate, work=work,
                           losses=losses, fired=fired)

    def _track_unfused(self, st, g, pstate, masked, base, obs_rgb, obs_depth, frags):
        """Seed loop shape: one dispatch per iteration, per-iteration host
        syncs for counters and the pruning boundary check."""
        cfg = self.cfg
        prune_cfg = cfg.prune
        xi = jnp.zeros(6)
        ostate = _pose_adam_zero()
        fr, px, gi, it_n = 0, 0, 0, 0
        losses, fired = [], []
        for _ in range(cfg.iters_track):
            loss, xi, ostate, g_params = self._call(
                st.track_iter, g, masked, xi, ostate, base, obs_rgb,
                obs_depth, frags)
            self.stats.syncs += 3   # frags.total, num_alive, masked&alive
            alive_eff = int(g.num_alive()) - int(jnp.sum(masked & g.alive))
            fr += int(frags.total)
            px += st.pixels
            gi += alive_eff
            it_n += 1
            losses.append(loss)
            did_fire = False
            if pstate is not None:
                pstate = pruning.accumulate(pstate, g_params, prune_cfg,
                                            alive=g.alive)
                self.stats.syncs += 1   # boundary check
                if int(pstate.iters_left) <= 0:
                    fresh = self._call(
                        st.build, g, pstate.masked,
                        lie.se3_exp(xi) @ base)
                    pstate, g, _ = pruning.interval_update(
                        pstate, g, fresh.count, prune_cfg)
                    masked = pstate.masked
                    frags = fresh
                    did_fire = True
            fired.append(did_fire)
        work = DeviceWork(fragments=fr, pixels=px, gaussians_iters=gi,
                          iterations=it_n, unstable_gaussians=0,
                          sched_programs=0, skipped_fragments=0,
                          densify_dropped=0,
                          frag_build_rows=(1 + sum(fired)) * g.capacity)
        return TrackResult(xi=xi, g=g, pstate=pstate, work=work,
                           losses=jnp.stack(losses), fired=np.asarray(fired))

    def map_frame(self, g, opt_state, masked, window: List[Tuple],
                  stable=None) -> MapResult:
        """Run the mapping iterations for one keyframe (or the frame-0
        bootstrap).  ``window`` is the host list of (rgb, depth, w2c np)
        keyframes, oldest first; every iteration optimizes the whole window
        jointly via one batched multi-view render.

        ``stable`` (an (N,) bool mask, sparse_opt mode) freezes stable
        Gaussians out of the Adam step, the fragment builds and the WSU
        schedule; ``None`` is the dense path."""
        cfg = self.cfg
        st = self.stage(1)
        w_len = len(window)
        assert 1 <= w_len <= cfg.map_window
        kf_w2c = jnp.asarray(np.stack([w[2] for w in window]))
        kf_rgb = jnp.asarray(np.stack([np.asarray(w[0]) for w in window]))
        kf_depth = jnp.asarray(np.stack([np.asarray(w[1]) for w in window]))
        if self.cfg.fused:
            work = device_work_zero()
            g, opt_state, work, losses, image = self._call(
                st.map_scan, g, masked, opt_state, kf_w2c, kf_rgb, kf_depth,
                work, stable)
            builds = w_len + cfg.iters_map // cfg.map_rebuild_stride
            return MapResult(g=g, opt_state=opt_state, work=work,
                             losses=losses, builds=builds, image=image)

        # -- unfused: per-iteration dispatches, per-iteration counter syncs.
        keep = None if stable is None else ~stable

        def build_slot(w2c):
            if keep is None:
                return self._call(st.build, g, masked, w2c), 0
            frs, sk = self._call(st.build_sparse, g, masked, keep, w2c)
            self.stats.syncs += 1
            return frs, int(sk)

        built = [build_slot(jnp.asarray(w[2])) for w in window]
        cache = [b[0] for b in built]
        skipped = [b[1] for b in built]
        builds = w_len
        # Slot totals (and per-slot program counts, the sparse counter)
        # fetched once per (re)build, not per iteration; the stacked window
        # cache is likewise re-stacked only when it changes.
        totals = [int(c.total) for c in cache]
        progs = [int(st.slot_programs(c)) for c in cache]
        self.stats.syncs += 2 * w_len
        stacked = stack_fragment_lists(cache)
        fr, px, gi, it_n, un, pr, sk_n = 0, 0, 0, 0, 0, 0, 0
        if stable is None:
            stable_bg = None
        else:
            # One stable-background render for the whole phase (stable rows
            # are bit-frozen), accounted once — same convention as the
            # fused scan.
            stable_bg, bg_total, bg_progs = self._call(
                st.stable_bg, g, masked, stable, kf_w2c)
            self.stats.syncs += 2
            fr += int(jnp.sum(bg_total))
            pr += int(jnp.sum(bg_progs))
        losses = []
        for it in range(cfg.iters_map):
            loss, g, opt_state = self._call(
                st.map_iter, g, masked, opt_state, kf_w2c, kf_rgb, kf_depth,
                stacked, None, kf_valid=None, unstable=keep,
                stable_bg=stable_bg)
            self.stats.syncs += 1   # num_alive
            n_alive = int(g.num_alive())
            n_opt = (n_alive if stable is None
                     else int(jnp.sum(g.alive & ~stable)))
            fr += sum(totals)
            px += w_len * st.pixels
            gi += w_len * n_alive
            un += w_len * n_opt
            pr += sum(progs)
            sk_n += sum(skipped)
            it_n += 1
            losses.append(loss)
            if (it + 1) % cfg.map_rebuild_stride == 0:
                slot = ((it + 1) // cfg.map_rebuild_stride - 1) % w_len
                cache[slot], skipped[slot] = build_slot(
                    jnp.asarray(window[slot][2]))
                totals[slot] = int(cache[slot].total)
                progs[slot] = int(st.slot_programs(cache[slot]))
                self.stats.syncs += 2
                stacked = stack_fragment_lists(cache)
                builds += 1
        work = DeviceWork(fragments=fr, pixels=px, gaussians_iters=gi,
                          iterations=it_n, unstable_gaussians=un,
                          sched_programs=pr, skipped_fragments=sk_n,
                          densify_dropped=0,
                          frag_build_rows=(builds + 1) * g.capacity)
        image = self._call(st.render_eval, g, masked, kf_w2c[-1])
        return MapResult(g=g, opt_state=opt_state, work=work,
                         losses=jnp.stack(losses), builds=builds, image=image)

    def geo_track_frame(self, base_w2c, pts_w, cols, valid, rgb, depth):
        """Photo-SLAM geometric tracking (no rendering, no pruning): the K
        pose iterations as one scan dispatch (fused) or K dispatches."""
        cfg = self.cfg
        if self._geo is None:
            key = (self.intr, cfg.lr_pose, cfg.iters_track)
            geo_scan, geo_vg = get_geo_scan(self.intr, cfg)
            if key not in _GEO_JIT_CACHE:
                _GEO_JIT_CACHE[key] = f32_jit(geo_scan)
            self._geo, self._geo_vg = _GEO_JIT_CACHE[key], geo_vg

        base = jnp.asarray(base_w2c)
        track_px = (self.intr.height // 4) * (self.intr.width // 4)
        work = DeviceWork(fragments=0, pixels=track_px * cfg.iters_track,
                          gaussians_iters=0, iterations=cfg.iters_track,
                          unstable_gaussians=0, sched_programs=0,
                          skipped_fragments=0, densify_dropped=0,
                          frag_build_rows=0)
        if cfg.fused:
            xi = self._call(self._geo, base, pts_w, cols, valid, rgb, depth)
            return xi, work
        popt = Adam(lr=cfg.lr_pose * 2)
        xi = jnp.zeros(6)
        pstate_pose = popt.init(xi)
        for _ in range(cfg.iters_track):
            _, gxi = self._call(self._geo_vg, xi, base, pts_w, cols, valid,
                                rgb, depth)
            upd, pstate_pose = popt.update(gxi, pstate_pose)
            xi = xi + upd
        return xi, work
