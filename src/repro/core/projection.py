"""Step 1 (Preprocessing): EWA projection of 3D Gaussians to screen space.

Fully differentiable pure-JAX; JAX autodiff through this module implements
the paper's Step-5 "Preprocessing BP" (2D gradients -> 3D Gaussian gradients
-> camera-pose gradients) with no hand-written adjoints.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.camera import Camera
from repro.core.gaussians import GaussianField, dot3
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
    """EWA projection, every 3x3 and 2x3 product written out as f32
    multiply-adds on (N,) component vectors: no (N,3,3) intermediate and no
    ``dot_general``, forward or backward (the pose gradient is a sum over N).
    """
    intr = cam.intrinsics
    W = cam.w2c[:3, :3]
    t = cam.w2c[:3, 3]

    mx, my, mz = g.mu[:, 0], g.mu[:, 1], g.mu[:, 2]
    px, py, z = (W[i, 0] * mx + W[i, 1] * my + W[i, 2] * mz + t[i] for i in range(3))
    z_safe = jnp.maximum(z, _NEAR)

    u = intr.fx * px / z_safe + intr.cx
    v = intr.fy * py / z_safe + intr.cy

    # Rows a, b of JW, with the perspective Jacobian
    # J = [[fx/z, 0, -fx x/z^2], [0, fy/z, -fy y/z^2]].
    inv_z = 1.0 / z_safe
    inv_z2 = inv_z * inv_z
    j00, j02 = intr.fx * inv_z, -intr.fx * px * inv_z2
    j11, j12 = intr.fy * inv_z, -intr.fy * py * inv_z2
    a = tuple(j00 * W[0, k] + j02 * W[2, k] for k in range(3))
    b = tuple(j11 * W[1, k] + j12 * W[2, k] for k in range(3))

    # cov2d = JW Sigma JW^T + blur I, as its three unique entries.
    sxx, sxy, sxz, syy, syz, szz = g.covariance_terms()

    def sigma_times(r):
        return (sxx * r[0] + sxy * r[1] + sxz * r[2],
                sxy * r[0] + syy * r[1] + syz * r[2],
                sxz * r[0] + syz * r[1] + szz * r[2])

    sa = sigma_times(a)
    c00 = dot3(a, sa) + _COV2D_BLUR
    c01 = dot3(b, sa)
    c11 = dot3(b, sigma_times(b)) + _COV2D_BLUR

    det = c00 * c11 - c01 * c01
    det_safe = jnp.maximum(det, 1e-12)
    inv_det = 1.0 / det_safe
    conic = jnp.stack([c11 * inv_det, -c01 * inv_det, c00 * inv_det], axis=-1)

    # Screen-space radius: 3 sigma of the major axis (non-differentiable use).
    mid = 0.5 * (c00 + c11)
    lam1 = mid + jnp.sqrt(jnp.maximum(mid * mid - det_safe, 0.0) + 1e-12)
    radius = jnp.ceil(3.0 * jnp.sqrt(jnp.maximum(lam1, 0.0)))

    onscreen = (
        (u + radius >= 0.0)
        & (u - radius <= intr.width)
        & (v + radius >= 0.0)
        & (v - radius <= intr.height)
    )
    valid = g.alive & (z > _NEAR) & (det > 1e-12) & onscreen

    return ProjectedGaussians(
        mu2d=jnp.stack([u, v], axis=-1),
        conic=conic,
        color=g.rgb(),
        opacity=g.opacity(),
        depth=z,
        radius=radius,
        valid=valid,
    )
