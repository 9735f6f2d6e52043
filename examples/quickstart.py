"""Quickstart: build a Gaussian field, render it differentiably through the
RasterAPI v2 (typed ``RasterPlan``), take a camera-pose gradient — the
primitive all of 3DGS-SLAM tracking is built from — and render a batch of
views in one call.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core import gaussians as G
from repro.core import lie
from repro.core.camera import Camera, Intrinsics, look_at
from repro.core.losses import psnr, slam_loss
from repro.core.raster_api import RasterPlan, registered_backends
from repro.core.render import render
from repro.core.sorting import make_tile_grid
from repro.launch.cache import use_compile_cache

use_compile_cache()

# --- a toy scene: 400 Gaussians on a plane + a blob ------------------------
key = jax.random.PRNGKey(0)
pts = jax.random.uniform(key, (400, 3), minval=-1, maxval=1) * jnp.array(
    [1.2, 0.8, 0.3]
) + jnp.array([0.0, 0.0, 2.5])
cols = jax.random.uniform(jax.random.PRNGKey(1), (400, 3))
field = G.from_points(pts, cols, capacity=512, scale=0.06, opacity=0.8)

intr = Intrinsics(fx=90.0, fy=90.0, cx=48.0, cy=32.0, width=96, height=64)
w2c = look_at(jnp.zeros(3), jnp.array([0.0, 0.0, 2.5]), jnp.array([0.0, -1.0, 0.0]))
cam = Camera(intr, w2c)

# --- a RasterPlan says HOW to rasterize: grid, backend (any name from the
#     registry), chunking, fragment capacity --------------------------------
plan = RasterPlan(grid=make_tile_grid(64, 96), backend="ref", capacity=64)
print(f"registered raster backends: {', '.join(registered_backends())}")

# --- render (Steps 1-3); swap plan.backend for the Pallas TPU kernels ------
out = render(field, cam, plan)
print(f"rendered {out.image.shape}, coverage={float(out.alpha.mean()):.3f}")

# --- pose gradient through the full pipeline (Steps 4-5) --------------------
obs_rgb = out.image  # pretend this view is the observation
obs_depth = jnp.where(out.alpha > 0.5, out.depth / jnp.maximum(out.alpha, 1e-6), 0.0)


def tracking_loss(xi):
    noisy = Camera(intr, lie.se3_exp(xi) @ w2c)
    # cached fragment lists from the first render are reused (Obs. 6)
    r = render(field, noisy, plan, frags=out.frags)
    return slam_loss(r.image, r.depth, r.alpha, obs_rgb, obs_depth)


xi0 = jnp.array([0.02, -0.01, 0.03, 0.01, -0.02, 0.005])  # pose error
g = jax.grad(tracking_loss)(xi0)
print("pose gradient:", [round(float(v), 4) for v in g])

# one normalized gradient step toward the true pose reduces the loss:
step = 0.01 * g / (jnp.linalg.norm(g) + 1e-9)
print(f"loss before {float(tracking_loss(xi0)):.5f} "
      f"after {float(tracking_loss(xi0 - step)):.5f}")
print(f"PSNR at true pose: {float(psnr(out.image, obs_rgb)):.1f} dB")

# --- batched multi-view rendering: a (B, 4, 4) pose stack renders B views
#     in ONE call, bit-identical to rendering them separately ----------------
w2c_batch = jnp.stack([
    w2c,
    look_at(jnp.array([0.15, 0.0, 0.0]), jnp.array([0.0, 0.0, 2.5]),
            jnp.array([0.0, -1.0, 0.0])),
])
batch = render(field, Camera(intr, w2c_batch), plan)
single = render(field, Camera(intr, w2c_batch[1]), plan)
same = bool(jnp.all(batch.image[1] == single.image))
print(f"batched render {batch.image.shape}; view 1 bit-equal to solo: {same}")
