"""End-to-end differentiable 3DGS rendering (Steps 1-5 of the paper).

``render`` composes: project (Step 1) -> fragment lists (Steps 1-2, 2;
cached/reused across §4.1 pruning intervals) -> rasterize (Step 3, via the
RasterAPI backend registry) -> background composite. JAX autodiff through the
whole function yields Rendering BP (Step 4, custom_vjp kernels + GMU) and
Preprocessing BP (Step 5, autodiff of ``project``) including camera-pose
gradients.

Canonical call shape (RasterAPI v2)::

    plan = RasterPlan(grid=grid, backend="pallas", capacity=128)
    out = render(g, cam, plan)                      # single view
    out = render(g, Camera(intr, w2c_batch), plan)  # (B,4,4) -> batched

A **leading camera batch axis** renders B views in one call: projection and
fragment building unroll per view (bit-identical to a per-view loop) and the
rasterizer runs one stacked-grid dispatch; every ``RenderOutput`` field gains
a leading ``B`` axis.  The legacy ``render(g, cam, grid, cfg=RenderConfig())``
signature forwards through a warn-once deprecation shim.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.camera import Camera
from repro.core.gaussians import GaussianField
from repro.core.projection import ProjectedGaussians, project
from repro.core.raster_api import RasterInputs, RasterPlan, warn_once
from repro.core.schedule import TileSchedule
from repro.core.sorting import FragmentLists, TileGrid, build_fragment_lists
from repro.kernels import ops
from repro.obs.profiling import scoped


class RenderConfig(NamedTuple):
    """Pre-v2 render knobs.  Kept for the legacy ``render(g, cam, grid, cfg)``
    signature; new code builds a :class:`RasterPlan` directly
    (``cfg.plan(grid)`` converts)."""

    capacity: int = 128          # fragments per tile (K)
    chunk: int = 16              # kernel chunk size (C)
    backend: str = "ref"         # any registered raster backend
    interpret: Optional[bool] = None  # Pallas interpret mode; None = CPU only
    background: tuple = (0.0, 0.0, 0.0)
    sched_bucket: int = 1        # WSU trip-count bucketing (schedule backend)

    def plan(self, grid: TileGrid,
             sched: Optional[TileSchedule] = None) -> RasterPlan:
        return RasterPlan(grid=grid, backend=self.backend, chunk=self.chunk,
                          capacity=self.capacity, interpret=self.interpret,
                          sched_bucket=self.sched_bucket, sched=sched)


class RenderOutput(NamedTuple):
    image: jnp.ndarray    # (H, W, 3) composited color        [(B, ...) batched]
    depth: jnp.ndarray    # (H, W) blended depth (premultiplied by alpha)
    alpha: jnp.ndarray    # (H, W) coverage = 1 - final transmittance
    final_t: jnp.ndarray  # (H, W)
    frags: FragmentLists
    proj: ProjectedGaussians


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _render_single(g: GaussianField, cam: Camera, plan: RasterPlan,
                   background, frags: Optional[FragmentLists],
                   keep=None) -> RenderOutput:
    proj = project(g, cam)
    if frags is None:
        frags = build_fragment_lists(proj, plan.grid, plan.capacity, keep=keep)
    # A schedule-backend plan without a carried sched derives one from the
    # frame's counts inside the backend (ops.build_plan_schedule).
    color_pm, depth_pm, final_t = ops.rasterize(
        RasterInputs.from_projection(proj, frags), plan)
    bg = jnp.asarray(background, jnp.float32)
    image = color_pm + final_t[..., None] * bg
    return RenderOutput(image=image, depth=depth_pm, alpha=1.0 - final_t,
                        final_t=final_t, frags=frags, proj=proj)


def _render_batched(g: GaussianField, cam: Camera, plan: RasterPlan,
                    background, frags: Optional[FragmentLists],
                    keep=None) -> RenderOutput:
    """B views in one call.  Projection/fragment building unroll per view in
    the trace (identical ops to a per-view loop — the bitwise anchor); the
    rasterizer itself is ONE stacked-grid dispatch."""
    num_views = cam.w2c.shape[0]
    projs = [project(g, Camera(cam.intrinsics, cam.w2c[b]))
             for b in range(num_views)]
    if frags is None:
        frag_views = [build_fragment_lists(projs[b], plan.grid, plan.capacity,
                                           keep=keep)
                      for b in range(num_views)]
        frags = _tree_stack(frag_views)
    proj = _tree_stack(projs)
    color_pm, depth_pm, final_t = ops.rasterize(
        RasterInputs.from_projection(proj, frags), plan)
    bg = jnp.asarray(background, jnp.float32)
    image = color_pm + final_t[..., None] * bg
    return RenderOutput(image=image, depth=depth_pm, alpha=1.0 - final_t,
                        final_t=final_t, frags=frags, proj=proj)


@scoped("raster")
def render(
    g: GaussianField,
    cam: Camera,
    plan: RasterPlan,
    cfg: Optional[RenderConfig] = None,
    frags: Optional[FragmentLists] = None,
    sched: Optional[TileSchedule] = None,
    *,
    background=(0.0, 0.0, 0.0),
    keep=None,
) -> RenderOutput:
    """Render ``g`` from ``cam`` under a :class:`RasterPlan`.

    ``cam.w2c`` of shape (4, 4) renders one view; (B, 4, 4) renders the B
    views batched (one stacked-grid rasterizer dispatch, outputs gain a
    leading B axis, **bit-identical** to rendering each view separately).
    Pass cached ``frags`` (leading B axis when batched) to reuse fragment
    lists across iterations; a ``schedule``-backend plan can carry the WSU
    schedule the same way (``plan.sched``).  ``keep`` (an (N,) bool mask)
    forwards to :func:`build_fragment_lists` when ``frags`` is None — the
    sparse stable/unstable path passes ``~stable`` so frozen Gaussians emit
    no fragments; ignored when cached ``frags`` are supplied.

    The legacy signature ``render(g, cam, grid, cfg=RenderConfig(), frags,
    sched)`` is still accepted (warn-once shim): ``cfg``/``sched`` fold into
    the plan and ``cfg.background`` wins.
    """
    if isinstance(plan, TileGrid):
        warn_once(
            "render",
            "render(g, cam, grid, cfg=RenderConfig(...)) is deprecated; "
            "pass a RasterPlan: render(g, cam, cfg.plan(grid)) "
            "(see README 'RasterAPI v2').",
            stacklevel=2,
        )
        rc = cfg if cfg is not None else RenderConfig()
        plan = rc.plan(plan, sched=sched)
        background = rc.background
    elif cfg is not None or sched is not None:
        raise TypeError(
            "render(g, cam, plan) does not take cfg/sched — fold them into "
            "the RasterPlan (cfg.plan(grid, sched=...))")

    if cam.w2c.ndim == 3:
        return _render_batched(g, cam, plan, background, frags, keep)
    return _render_single(g, cam, plan, background, frags, keep)
