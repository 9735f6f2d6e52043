"""Classical (non-rendering) tracking for the Photo-SLAM base algorithm.

Photo-SLAM tracks with geometric optimization (ORB + motion-only BA) instead
of differentiating through the renderer; RTGS therefore applies its
techniques only to Photo-SLAM's *mapping* BP (§6.1). We implement the
TPU-friendly equivalent: dense frame-to-frame direct odometry — backproject
the previous frame's depth, reproject into the current frame, minimize
photometric + depth residuals over a subsampled pixel grid. No Gaussians,
no rasterizer: tracking cost is independent of the map, which is exactly
the property that makes Photo-SLAM's tracking fast (Tab. 2 footnote 2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lie
from repro.core.camera import Intrinsics


def bilinear_sample(img: jnp.ndarray, uv: jnp.ndarray) -> jnp.ndarray:
    """Sample (H, W, C) or (H, W) at continuous pixel coords uv (P, 2)."""
    squeeze = img.ndim == 2
    if squeeze:
        img = img[..., None]
    h, w = img.shape[:2]
    u = jnp.clip(uv[:, 0] - 0.5, 0.0, w - 1.001)
    v = jnp.clip(uv[:, 1] - 0.5, 0.0, h - 1.001)
    u0, v0 = jnp.floor(u).astype(jnp.int32), jnp.floor(v).astype(jnp.int32)
    du, dv = (u - u0)[:, None], (v - v0)[:, None]
    p00 = img[v0, u0]
    p01 = img[v0, u0 + 1]
    p10 = img[v0 + 1, u0]
    p11 = img[v0 + 1, u0 + 1]
    out = (
        p00 * (1 - du) * (1 - dv)
        + p01 * du * (1 - dv)
        + p10 * (1 - du) * dv
        + p11 * du * dv
    )
    return out[:, 0] if squeeze else out


def backproject_grid(
    rgb: jnp.ndarray, depth: jnp.ndarray, w2c: jnp.ndarray, intr: Intrinsics,
    stride: int = 4,
):
    """World-space points + colors for a strided pixel grid of one frame."""
    ys = jnp.arange(0, intr.height, stride, dtype=jnp.float32) + 0.5
    xs = jnp.arange(0, intr.width, stride, dtype=jnp.float32) + 0.5
    vv, uu = jnp.meshgrid(ys, xs, indexing="ij")
    uu, vv = uu.reshape(-1), vv.reshape(-1)
    uv = jnp.stack([uu, vv], -1)
    d = bilinear_sample(depth, uv)
    c = bilinear_sample(rgb, uv)
    x_cam = jnp.stack(
        [(uu - intr.cx) / intr.fx * d, (vv - intr.cy) / intr.fy * d, d], -1
    )
    c2w = lie.se3_inverse(w2c)
    x_world = x_cam @ c2w[:3, :3].T + c2w[:3, 3]
    valid = d > 1e-3
    return x_world, c, d, valid


def make_geometric_tracker(intr: Intrinsics, lambda_pho: float = 0.7):
    """Returns a jitted loss(xi, base_w2c, points, colors, valid, rgb, depth)."""

    def loss_fn(xi, base_w2c, pts_w, cols, valid, cur_rgb, cur_depth):
        w2c = lie.se3_exp(xi) @ base_w2c
        x_cam = pts_w @ w2c[:3, :3].T + w2c[:3, 3]
        z = jnp.maximum(x_cam[:, 2], 1e-3)
        uv = jnp.stack(
            [intr.fx * x_cam[:, 0] / z + intr.cx, intr.fy * x_cam[:, 1] / z + intr.cy],
            -1,
        )
        inb = (
            (uv[:, 0] > 1) & (uv[:, 0] < intr.width - 1)
            & (uv[:, 1] > 1) & (uv[:, 1] < intr.height - 1)
            & valid & (x_cam[:, 2] > 1e-3)
        )
        w = inb.astype(jnp.float32)
        wsum = jnp.maximum(w.sum(), 1.0)
        samp_rgb = bilinear_sample(cur_rgb, uv)
        samp_d = bilinear_sample(cur_depth, uv)
        e_pho = jnp.sum(jnp.abs(samp_rgb - cols).mean(-1) * w) / wsum
        d_ok = w * (samp_d > 1e-3).astype(jnp.float32)
        e_geo = jnp.sum(jnp.abs(samp_d - z) * d_ok) / jnp.maximum(d_ok.sum(), 1.0)
        return lambda_pho * e_pho + (1 - lambda_pho) * e_geo

    return lie.f32_jit(jax.value_and_grad(loss_fn))
