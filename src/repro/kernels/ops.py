"""Differentiable rasterization ops behind the RasterAPI backend registry.

The public entry point is ``rasterize(inputs, plan)`` with
:class:`~repro.core.raster_api.RasterInputs` /
:class:`~repro.core.raster_api.RasterPlan` pytrees; a warn-once shim keeps
the pre-v2 seven-positional-array signature alive for old callers.

Four built-in backends self-register via ``register_backend`` (all share one
blending semantics; new kernel variants plug in the same way without touching
``core/render.py``):

  ref          pure-jnp oracle; gradients via JAX autodiff. Ground truth for
               every kernel test; also the fastest path on this CPU container.
  pallas       forward kernel stashes fragment alphas (R&B Buffer); backward
               kernel replays with multiplies only and merges gradients
               in-kernel over pixels (GMU L1), then GMU L2 run-reduction maps
               (tile, fragment) rows to per-Gaussian gradients.
  pallas_norb  paper-baseline ablation WITHOUT the R&B Buffer: the backward
               re-runs the forward kernel to regenerate the stash (alpha
               recompute incl. exp), then proceeds as above. The HLO-FLOP
               delta vs. ``pallas`` is the paper's 20->4 cycle claim in
               roofline terms.
  schedule     the ``pallas`` path under a WSU :class:`TileSchedule`
               (repro/core/schedule.py): one program per balanced tile pair
               via scalar-prefetch block indexing, chunk loops bounded by
               actual load, backward replaying the same schedule + slot-order
               stash. Bit-identical outputs/gradients to ``pallas``.

**Batched multi-view rendering:** when every ``RasterInputs`` leaf carries a
leading view axis ``B``, the Pallas backends run ONE kernel dispatch over a
*stacked grid* of ``B*T`` tile programs (``tiles_per_view`` in
kernels/tile_render*.py) while the cheap pack/unpack/merge stages unroll per
view — so batched outputs and gradients are **bit-identical** to rasterizing
each view separately (the PR 2 invariant: per-program code paths, including
the shared fori_loop tile-loop helpers, are reused as-is).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.raster_api import (
    RasterInputs,
    RasterPlan,
    get_backend,
    register_backend,
    warn_once,
)
from repro.core.schedule import TileSchedule, build_schedule
from repro.core.sorting import FragmentLists, TileGrid
from repro.kernels import gmu, ref
from repro.kernels.tile_render import tile_render_fwd, tile_render_fwd_sched
from repro.kernels.tile_render_bp import tile_render_bwd, tile_render_bwd_sched
from repro.obs.profiling import scoped

_FLOAT0 = jax.dtypes.float0


def _pack_attrs(mu2d, conic, color, opacity, depth, frag_idx):
    """Gather (N,)-arrays into the packed (T, 12, K) tile layout.

    Differentiable (used directly by the ref backend; the pallas backend
    re-derives its backward through the GMU instead).
    """
    safe = jnp.maximum(frag_idx, 0)
    present = frag_idx >= 0

    def take(x):
        return jnp.where(present, x[safe], 0.0)

    return jnp.stack(
        [
            take(mu2d[:, 0]), take(mu2d[:, 1]),
            take(conic[:, 0]), take(conic[:, 1]), take(conic[:, 2]),
            take(color[:, 0]), take(color[:, 1]), take(color[:, 2]),
            take(opacity), take(depth),
            present.astype(jnp.float32),
            jnp.zeros_like(frag_idx, jnp.float32),
        ],
        axis=1,
    )


def _view(inputs: RasterInputs, b) -> RasterInputs:
    return jax.tree.map(lambda x: x[b], inputs)


def _pack_views(inputs: RasterInputs, views: int | None):
    """Packed attrs + flat counts for 1 or B stacked views.

    Per-view packing unrolls in the trace (identical ops to the per-frame
    loop — the bit-exactness anchor); only the kernel sees the stack."""
    if views is None:
        attrs = _pack_attrs(inputs.mu2d, inputs.conic, inputs.color,
                            inputs.opacity, inputs.depth, inputs.frags.idx)
        return attrs, inputs.frags.count
    packed = [
        _pack_attrs(v.mu2d, v.conic, v.color, v.opacity, v.depth, v.frags.idx)
        for v in (_view(inputs, b) for b in range(views))
    ]
    return jnp.concatenate(packed), inputs.frags.count.reshape(-1)


def _zero_tangents(tree):
    """float0 cotangents for index-plumbing pytrees (frags, schedules)."""
    return jax.tree.map(lambda x: np.zeros(x.shape, _FLOAT0), tree)


# ---------------------------------------------------------------------------
# ref backend
# ---------------------------------------------------------------------------


def _ref_rasterize_single(inputs: RasterInputs, grid: TileGrid):
    attrs = _pack_attrs(inputs.mu2d, inputs.conic, inputs.color,
                        inputs.opacity, inputs.depth, inputs.frags.idx)
    color_t, depth_t, finalt_t = ref.rasterize_tiles(attrs, grid)
    return (
        ref.tiles_to_image(color_t, grid),
        ref.tiles_to_image(depth_t, grid),
        ref.tiles_to_image(finalt_t, grid),
    )


@register_backend("ref")
def _ref_backend(inputs: RasterInputs, plan: RasterPlan):
    views = inputs.views
    if views is None:
        return _ref_rasterize_single(inputs, plan.grid)
    outs = [_ref_rasterize_single(_view(inputs, b), plan.grid)
            for b in range(views)]
    return tuple(jnp.stack([o[i] for o in outs]) for i in range(3))


# ---------------------------------------------------------------------------
# pallas / pallas_norb backends
# ---------------------------------------------------------------------------


def _make_pallas_rasterize(grid: TileGrid, chunk: int, interpret: bool,
                           reuse_stash: bool, views: int | None):
    """Build the custom_vjp pallas op for a fixed tile grid and view count
    (``views=None`` = single view; otherwise one stacked-grid dispatch)."""
    tiles = grid.num_tiles
    nv = views or 1

    def _images(color_t, depth_t, finalt_t):
        outs = []
        for b in range(nv):
            sl = slice(b * tiles, (b + 1) * tiles)
            outs.append((
                ref.tiles_to_image(jnp.moveaxis(color_t[sl], 1, 2), grid),
                ref.tiles_to_image(depth_t[sl], grid),
                ref.tiles_to_image(finalt_t[sl], grid),
            ))
        if views is None:
            return outs[0]
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(3))

    @jax.custom_vjp
    def rasterize(inputs: RasterInputs):
        out, _ = _fwd(inputs)
        return out

    def _fwd(inputs: RasterInputs):
        attrs, count = _pack_views(inputs, views)
        color_t, depth_t, finalt_t, stash = tile_render_fwd(
            attrs, count, grid, chunk=chunk, interpret=interpret,
            tiles_per_view=tiles,
        )
        out = _images(color_t, depth_t, finalt_t)
        residuals = (attrs, count, inputs.frags,
                     stash if reuse_stash else None, inputs.mu2d.shape[-2])
        return out, residuals

    def _bwd(residuals, cotangents):
        attrs, count, frags, stash, n = residuals
        g_img, g_depth, g_finalt = cotangents

        if stash is None:
            # pallas_norb: regenerate the stash — the alpha recompute the
            # R&B Buffer exists to avoid.
            _, _, _, stash = tile_render_fwd(
                attrs, count, grid, chunk=chunk, interpret=interpret,
                tiles_per_view=tiles,
            )

        def cot_tiles(b):
            gi = (g_img, g_depth, g_finalt) if views is None else (
                g_img[b], g_depth[b], g_finalt[b])
            return (
                jnp.moveaxis(ref.image_to_tiles(gi[0], grid), 2, 1),  # (T,3,256)
                ref.image_to_tiles(gi[1], grid),
                ref.image_to_tiles(gi[2], grid),
            )

        cots = [cot_tiles(b) for b in range(nv)]
        g_color_t = cots[0][0] if nv == 1 else jnp.concatenate([c[0] for c in cots])
        g_depth_t = cots[0][1] if nv == 1 else jnp.concatenate([c[1] for c in cots])
        g_finalt_t = cots[0][2] if nv == 1 else jnp.concatenate([c[2] for c in cots])

        tile_grads = tile_render_bwd(
            attrs, count, stash, g_color_t, g_depth_t, g_finalt_t,
            grid, chunk=chunk, interpret=interpret, tiles_per_view=tiles,
        )  # (B*T, 10, K) — already pixel-merged (GMU L1)

        merged_views = []
        for b in range(nv):
            tg = tile_grads[b * tiles:(b + 1) * tiles]
            flat = jnp.moveaxis(tg, 1, 2).reshape(-1, 10)  # (T*K, 10)
            ids = (frags.idx if views is None else frags.idx[b]).reshape(-1)
            merged_views.append(
                gmu.segment_merge(flat, ids, num_segments=n))  # (N, 10) GMU L2
        merged = merged_views[0] if views is None else jnp.stack(merged_views)

        g_inputs = RasterInputs(
            mu2d=merged[..., 0:2],
            conic=merged[..., 2:5],
            color=merged[..., 5:8],
            opacity=merged[..., 8],
            depth=merged[..., 9],
            frags=_zero_tangents(frags),
        )
        return (g_inputs,)

    rasterize.defvjp(_fwd, _bwd)
    return rasterize


@functools.lru_cache(maxsize=64)
def _get_pallas_op(grid: TileGrid, chunk: int, interpret: bool,
                   reuse_stash: bool, views: int | None):
    return _make_pallas_rasterize(grid, chunk, interpret, reuse_stash, views)


@register_backend("pallas")
def _pallas_backend(inputs: RasterInputs, plan: RasterPlan):
    op = _get_pallas_op(plan.grid, plan.chunk, plan.interpret, True,
                        inputs.views)
    return op(inputs)


@register_backend("pallas_norb")
def _pallas_norb_backend(inputs: RasterInputs, plan: RasterPlan):
    op = _get_pallas_op(plan.grid, plan.chunk, plan.interpret, False,
                        inputs.views)
    return op(inputs)


# ---------------------------------------------------------------------------
# schedule backend (WSU)
# ---------------------------------------------------------------------------


def _flatten_sched(sched: TileSchedule, tiles: int, views: int | None):
    """Global slot arrays for the stacked kernel: per-view perms offset to
    global attr rows (view*T + tile), trips concatenated."""
    if views is None:
        return sched.perm, sched.trips
    offs = (jnp.arange(views, dtype=jnp.int32) * tiles)[:, None]
    return (sched.perm + offs).reshape(-1), sched.trips.reshape(-1)


def _make_sched_rasterize(grid: TileGrid, chunk: int, interpret: bool,
                          views: int | None):
    """Build the custom_vjp WSU-scheduled op for a fixed tile grid and view
    count.

    The schedule is an explicit operand pytree so the engine can carry it
    through its ``lax.scan`` and feed it here without retracing; its arrays
    are index plumbing like ``frags.idx`` (zero cotangent)."""
    tiles = grid.num_tiles
    nv = views or 1

    @jax.custom_vjp
    def rasterize(inputs: RasterInputs, sched: TileSchedule):
        out, _ = _fwd(inputs, sched)
        return out

    def _fwd(inputs: RasterInputs, sched: TileSchedule):
        attrs, _ = _pack_views(inputs, views)
        perm_flat, trips_flat = _flatten_sched(sched, tiles, views)
        color_s, depth_s, finalt_s, stash_s = tile_render_fwd_sched(
            attrs, perm_flat, trips_flat, grid, chunk=chunk,
            interpret=interpret, tiles_per_view=tiles,
        )
        slots = perm_flat.shape[0] // nv

        # Slot order -> tile order per view (drops the pad slot, if any).
        outs = []
        for b in range(nv):
            sl = slice(b * slots, (b + 1) * slots)
            inv = sched.inv if views is None else sched.inv[b]
            outs.append((
                ref.tiles_to_image(
                    jnp.moveaxis(jnp.take(color_s[sl], inv, axis=0), 1, 2), grid),
                ref.tiles_to_image(jnp.take(depth_s[sl], inv, axis=0), grid),
                ref.tiles_to_image(jnp.take(finalt_s[sl], inv, axis=0), grid),
            ))
        if views is None:
            out = outs[0]
        else:
            out = tuple(jnp.stack([o[i] for o in outs]) for i in range(3))
        residuals = (attrs, inputs.frags, stash_s, sched,
                     inputs.mu2d.shape[-2])
        return out, residuals

    def _bwd(residuals, cotangents):
        attrs, frags, stash_s, sched, n = residuals
        g_img, g_depth, g_finalt = cotangents
        # Pure index math — cheaper to recompute than to hold in residuals.
        perm_flat, trips_flat = _flatten_sched(sched, tiles, views)
        slots = perm_flat.shape[0] // nv

        # Cotangents to slot order; the stash is already slot-ordered (the
        # backward replays the forward's schedule — no stash shuffle).
        cots = []
        for b in range(nv):
            gi = (g_img, g_depth, g_finalt) if views is None else (
                g_img[b], g_depth[b], g_finalt[b])
            perm = sched.perm if views is None else sched.perm[b]
            cots.append((
                jnp.take(jnp.moveaxis(ref.image_to_tiles(gi[0], grid), 2, 1),
                         perm, axis=0),
                jnp.take(ref.image_to_tiles(gi[1], grid), perm, axis=0),
                jnp.take(ref.image_to_tiles(gi[2], grid), perm, axis=0),
            ))
        g_color_s = cots[0][0] if nv == 1 else jnp.concatenate([c[0] for c in cots])
        g_depth_s = cots[0][1] if nv == 1 else jnp.concatenate([c[1] for c in cots])
        g_finalt_s = cots[0][2] if nv == 1 else jnp.concatenate([c[2] for c in cots])

        sched_grads = tile_render_bwd_sched(
            attrs, perm_flat, trips_flat, stash_s, g_color_s, g_depth_s,
            g_finalt_s, grid, chunk=chunk, interpret=interpret,
            tiles_per_view=tiles,
        )  # (B*S, 10, K) slot order, pixel-merged (GMU L1)

        merged_views = []
        for b in range(nv):
            sl = slice(b * slots, (b + 1) * slots)
            inv = sched.inv if views is None else sched.inv[b]
            # Back to tile order BEFORE the level-2 merge: the merge's float
            # summation order then matches the unscheduled path exactly.
            tile_grads = jnp.take(sched_grads[sl], inv, axis=0)  # (T, 10, K)
            flat = jnp.moveaxis(tile_grads, 1, 2).reshape(-1, 10)
            ids = (frags.idx if views is None else frags.idx[b]).reshape(-1)
            merged_views.append(gmu.segment_merge(flat, ids, num_segments=n))
        merged = merged_views[0] if views is None else jnp.stack(merged_views)

        g_inputs = RasterInputs(
            mu2d=merged[..., 0:2],
            conic=merged[..., 2:5],
            color=merged[..., 5:8],
            opacity=merged[..., 8],
            depth=merged[..., 9],
            frags=_zero_tangents(frags),
        )
        return (g_inputs, _zero_tangents(sched))

    rasterize.defvjp(_fwd, _bwd)
    return rasterize


@functools.lru_cache(maxsize=64)
def _get_sched_op(grid: TileGrid, chunk: int, interpret: bool,
                  views: int | None):
    return _make_sched_rasterize(grid, chunk, interpret, views)


def build_plan_schedule(frags: FragmentLists, plan: RasterPlan) -> TileSchedule:
    """Schedule(s) for ``frags`` under ``plan`` — per view when ``frags``
    carries a leading view axis (leaves then stack to (B, S)/(B, T))."""
    if frags.count.ndim == 1:
        return build_schedule(frags.count, plan.chunk,
                              bucket=plan.sched_bucket,
                              max_trips=plan.max_trips)
    per = [build_schedule(frags.count[b], plan.chunk,
                          bucket=plan.sched_bucket, max_trips=plan.max_trips)
           for b in range(frags.count.shape[0])]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


@register_backend("schedule")
def _schedule_backend(inputs: RasterInputs, plan: RasterPlan):
    sched = plan.sched
    if sched is None:
        # No carried schedule (per-iteration caller): derive from this
        # frame's counts — the redundancy a carried schedule removes.
        sched = build_plan_schedule(inputs.frags, plan)
    want = 1 if inputs.views is None else 2
    if sched.perm.ndim != want:
        kind = ("per-view (B, S) schedules (e.g. from build_plan_schedule)"
                if inputs.views else "a single-view (S,) schedule")
        raise ValueError(
            f"schedule backend: carried sched.perm is {sched.perm.ndim}-D "
            f"but these inputs need {kind}")
    op = _get_sched_op(plan.grid, plan.chunk, plan.interpret, inputs.views)
    return op(inputs, sched)


# ---------------------------------------------------------------------------
# public entry point (+ the pre-v2 positional-signature shim)
# ---------------------------------------------------------------------------


@scoped("raster")
def rasterize(*args, **kwargs):
    """Rasterize projected Gaussians into (H,W,3) premultiplied color,
    (H,W) blended depth and (H,W) final transmittance (leading view axis
    ``B`` on every output when ``inputs`` is batched).

    Canonical signature::

        rasterize(inputs: RasterInputs, plan: RasterPlan)

    Differentiable in all float leaves of ``inputs``; ``frags`` (and the
    plan's schedule, for the ``schedule`` backend) are index plumbing (zero
    cotangent).  The backend is resolved by name through the RasterAPI
    registry — unknown names raise with the registered list.

    The pre-v2 positional form ``rasterize(mu2d, conic, color, opacity,
    depth, frag_idx, count, *, grid=..., backend=..., chunk=...,
    interpret=..., sched=...)`` still works behind a warn-once
    DeprecationWarning shim.
    """
    if (args and isinstance(args[0], RasterInputs)) or "inputs" in kwargs:
        inputs = args[0] if args else kwargs.pop("inputs")
        if len(args) > 1:
            plan = args[1]
        elif "plan" in kwargs:
            plan = kwargs.pop("plan")
        else:
            raise TypeError("rasterize(inputs, plan): missing required "
                            "argument 'plan' (a RasterPlan)")
        if len(args) > 2 or kwargs:
            raise TypeError("rasterize(inputs, plan) takes no extra arguments")
        return get_backend(plan.backend)(inputs, plan)

    warn_once(
        "ops.rasterize",
        "ops.rasterize(mu2d, conic, color, opacity, depth, frag_idx, count, "
        "grid=..., backend=...) is deprecated; build a RasterInputs / "
        "RasterPlan pair and call ops.rasterize(inputs, plan) instead "
        "(see README 'RasterAPI v2').",
    )
    names = ("mu2d", "conic", "color", "opacity", "depth", "frag_idx", "count")
    if len(args) > len(names):
        raise TypeError(f"rasterize() takes at most {len(names)} positional "
                        "arguments in its legacy form")
    vals = list(args)
    for name in names[len(args):]:   # pre-v2 operands were positional-or-keyword
        if name not in kwargs:
            raise TypeError(f"rasterize() missing legacy operand {name!r} "
                            "(or pass RasterInputs/RasterPlan instead)")
        vals.append(kwargs.pop(name))
    mu2d, conic, color, opacity, depth, frag_idx, count = vals
    grid = kwargs.pop("grid")
    backend = kwargs.pop("backend", "ref")
    chunk = kwargs.pop("chunk", 16)
    interpret = kwargs.pop("interpret", None)
    sched = kwargs.pop("sched", None)
    if kwargs:
        raise TypeError(f"unknown rasterize() kwargs: {sorted(kwargs)}")
    zero = jnp.zeros((), jnp.int32)
    inputs = RasterInputs(
        mu2d=mu2d, conic=conic, color=color, opacity=opacity, depth=depth,
        frags=FragmentLists(idx=frag_idx, count=count, overflow=zero,
                            total=zero),
    )
    plan = RasterPlan(grid=grid, backend=backend, chunk=chunk,
                      capacity=frag_idx.shape[-1], interpret=interpret,
                      sched=sched)
    return get_backend(backend)(inputs, plan)
