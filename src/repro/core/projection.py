"""Step 1 (Preprocessing): EWA projection of 3D Gaussians to screen space.

Fully differentiable pure-JAX; JAX autodiff through this module implements
the paper's Step-5 "Preprocessing BP" (2D gradients -> 3D Gaussian gradients
-> camera-pose gradients) with no hand-written adjoints.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.camera import Camera
from repro.core.gaussians import GaussianField
from repro.obs.profiling import scoped

# Low-pass filter added to 2D covariance (standard 3DGS; guarantees a
# minimum splat size of ~0.3px so conics stay invertible).
_COV2D_BLUR = 0.3
_NEAR = 0.05


class ProjectedGaussians(NamedTuple):
    """Per-Gaussian 2D attributes (the paper's G^2D)."""

    mu2d: jnp.ndarray    # (N, 2) pixel coords
    conic: jnp.ndarray   # (N, 3) upper-triangular inverse 2D covariance (a,b,c)
    color: jnp.ndarray   # (N, 3) rgb in [0,1]
    opacity: jnp.ndarray  # (N,)
    depth: jnp.ndarray   # (N,) camera-space z
    radius: jnp.ndarray  # (N,) screen-space extent in px (non-diff, for tiling)
    valid: jnp.ndarray   # (N,) bool — alive, in front of camera, on screen


@scoped("project")
def project(g: GaussianField, cam: Camera) -> ProjectedGaussians:
    intr = cam.intrinsics
    W = cam.w2c[:3, :3]
    t = cam.w2c[:3, 3]

    p_cam = g.mu @ W.T + t  # (N,3)
    z = p_cam[:, 2]
    z_safe = jnp.maximum(z, _NEAR)

    mu2d = jnp.stack(
        [
            intr.fx * p_cam[:, 0] / z_safe + intr.cx,
            intr.fy * p_cam[:, 1] / z_safe + intr.cy,
        ],
        axis=-1,
    )

    # Perspective Jacobian J (N,2,3).
    inv_z = 1.0 / z_safe
    inv_z2 = inv_z * inv_z
    zeros = jnp.zeros_like(z)
    J = jnp.stack(
        [
            jnp.stack([intr.fx * inv_z, zeros, -intr.fx * p_cam[:, 0] * inv_z2], -1),
            jnp.stack([zeros, intr.fy * inv_z, -intr.fy * p_cam[:, 1] * inv_z2], -1),
        ],
        axis=-2,
    )

    cov3d = g.covariance()  # (N,3,3)
    JW = J @ W  # (N,2,3)
    cov2d = JW @ cov3d @ jnp.swapaxes(JW, -1, -2)  # (N,2,2)
    cov2d = cov2d + _COV2D_BLUR * jnp.eye(2, dtype=cov2d.dtype)

    det = cov2d[:, 0, 0] * cov2d[:, 1, 1] - cov2d[:, 0, 1] * cov2d[:, 1, 0]
    det_safe = jnp.maximum(det, 1e-12)
    inv_det = 1.0 / det_safe
    conic = jnp.stack(
        [cov2d[:, 1, 1] * inv_det, -cov2d[:, 0, 1] * inv_det, cov2d[:, 0, 0] * inv_det],
        axis=-1,
    )

    # Screen-space radius: 3 sigma of the major axis (non-differentiable use).
    mid = 0.5 * (cov2d[:, 0, 0] + cov2d[:, 1, 1])
    lam1 = mid + jnp.sqrt(jnp.maximum(mid * mid - det_safe, 0.0) + 1e-12)
    radius = jnp.ceil(3.0 * jnp.sqrt(jnp.maximum(lam1, 0.0)))

    margin = radius
    onscreen = (
        (mu2d[:, 0] + margin >= 0.0)
        & (mu2d[:, 0] - margin <= intr.width)
        & (mu2d[:, 1] + margin >= 0.0)
        & (mu2d[:, 1] - margin <= intr.height)
    )
    valid = g.alive & (z > _NEAR) & (det > 1e-12) & onscreen

    return ProjectedGaussians(
        mu2d=mu2d,
        conic=conic,
        color=g.rgb(),
        opacity=g.opacity(),
        depth=z,
        radius=radius,
        valid=valid,
    )
