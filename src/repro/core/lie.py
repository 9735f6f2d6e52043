"""SO(3)/SE(3) Lie-group operations for camera-pose optimization.

Tracking in 3DGS-SLAM optimizes a 6-DoF camera pose. We parameterize updates
as tangent-space deltas around the current pose (left-multiplication), which
is what MonoGS/GS-SLAM do on GPU; JAX autodiff through ``se3_exp`` provides
the paper's Step-5 pose gradients (dL/dP) for free.

All coefficient functions use the "double-where" trick so gradients at the
theta=0 linearization point (where every tracking iteration starts) are
exact and NaN-free.

All functions are pure, jit-safe, float32, and batched-friendly (leading dims
broadcast).  :func:`f32_jit` is the jit the SLAM steps are built with: it
keeps these products in float32 on every backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_SERIES_CUT = 1e-8


def hat(w: jnp.ndarray) -> jnp.ndarray:
    """so(3) hat operator: (…,3) -> (…,3,3) skew-symmetric matrix."""
    wx, wy, wz = w[..., 0], w[..., 1], w[..., 2]
    z = jnp.zeros_like(wx)
    return jnp.stack(
        [
            jnp.stack([z, -wz, wy], axis=-1),
            jnp.stack([wz, z, -wx], axis=-1),
            jnp.stack([-wy, wx, z], axis=-1),
        ],
        axis=-2,
    )


# (t - sin t)/t^3 and (1 - a/2b)/t^2 suffer catastrophic f32 cancellation
# well above the NaN threshold (at theta=3e-3 the closed form is ~1% off,
# caught by the hypothesis round-trip test) — series until theta < 0.1.
_CANCEL_CUT = 1e-2


def _abc(theta2: jnp.ndarray):
    """Rodrigues coefficients a=sin(t)/t, b=(1-cos t)/t^2, c=(t-sin t)/t^3
    with NaN-free series fallbacks (double-where)."""
    use_series = theta2 < _SERIES_CUT
    t2 = jnp.where(use_series, 1.0, theta2)  # safe denominator
    t = jnp.sqrt(t2)
    a = jnp.where(use_series, 1.0 - theta2 / 6.0, jnp.sin(t) / t)
    b = jnp.where(use_series, 0.5 - theta2 / 24.0, (1.0 - jnp.cos(t)) / t2)
    use_c_series = theta2 < _CANCEL_CUT
    c = jnp.where(
        use_c_series,
        1.0 / 6.0 - theta2 / 120.0,
        (t - jnp.sin(t)) / (t2 * t),
    )
    return a, b, c


def so3_exp(w: jnp.ndarray) -> jnp.ndarray:
    """Rodrigues: (…,3) axis-angle -> (…,3,3) rotation matrix."""
    theta2 = jnp.sum(w * w, axis=-1, keepdims=True)[..., None]  # (…,1,1)
    a, b, _ = _abc(theta2)
    W = hat(w)
    W2 = W @ W
    eye = jnp.eye(3, dtype=w.dtype)
    return eye + a * W + b * W2


def so3_log(R: jnp.ndarray) -> jnp.ndarray:
    """Inverse of so3_exp: (…,3,3) -> (…,3). Valid for |theta| < pi."""
    trace = R[..., 0, 0] + R[..., 1, 1] + R[..., 2, 2]
    cos_t = jnp.clip((trace - 1.0) * 0.5, -1.0 + 1e-7, 1.0 - 1e-7)
    theta = jnp.arccos(cos_t)
    vee = jnp.stack(
        [
            R[..., 2, 1] - R[..., 1, 2],
            R[..., 0, 2] - R[..., 2, 0],
            R[..., 1, 0] - R[..., 0, 1],
        ],
        axis=-1,
    )
    small = theta < 1e-6
    theta_safe = jnp.where(small, 1.0, theta)[..., None]
    scale = jnp.where(
        small[..., None],
        0.5 + theta[..., None] ** 2 / 12.0,
        theta_safe / (2.0 * jnp.sin(theta_safe)),
    )
    return scale * vee


def se3_exp(xi: jnp.ndarray) -> jnp.ndarray:
    """se(3) exp: (…,6) [rho, w] -> (…,4,4) homogeneous transform."""
    rho, w = xi[..., :3], xi[..., 3:]
    theta2 = jnp.sum(w * w, axis=-1, keepdims=True)[..., None]
    a, b, c = _abc(theta2)
    W = hat(w)
    W2 = W @ W
    eye = jnp.eye(3, dtype=xi.dtype)
    R = eye + a * W + b * W2
    V = eye + b * W + c * W2
    t = jnp.einsum("...ij,...j->...i", V, rho)
    top = jnp.concatenate([R, t[..., None]], axis=-1)
    bottom = jnp.broadcast_to(
        jnp.array([0.0, 0.0, 0.0, 1.0], dtype=xi.dtype), top.shape[:-2] + (1, 4)
    )
    return jnp.concatenate([top, bottom], axis=-2)


def se3_log(T: jnp.ndarray) -> jnp.ndarray:
    """Inverse of se3_exp: (…,4,4) -> (…,6)."""
    R, t = T[..., :3, :3], T[..., :3, 3]
    w = so3_log(R)
    theta2 = jnp.sum(w * w, axis=-1, keepdims=True)[..., None]
    a, b, _ = _abc(theta2)
    W = hat(w)
    W2 = W @ W
    use_series = theta2 < _CANCEL_CUT  # 1 - a/2b cancels in f32 below this
    t2 = jnp.where(use_series, 1.0, theta2)
    coef = jnp.where(use_series, 1.0 / 12.0 + theta2 / 720.0, (1.0 - a / (2.0 * b)) / t2)
    eye = jnp.eye(3, dtype=T.dtype)
    Vinv = eye - 0.5 * W + coef * W2
    rho = jnp.einsum("...ij,...j->...i", Vinv, t)
    return jnp.concatenate([rho, w], axis=-1)


def se3_compose(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Compose homogeneous transforms: A @ B."""
    return A @ B


def se3_inverse(T: jnp.ndarray) -> jnp.ndarray:
    R, t = T[..., :3, :3], T[..., :3, 3]
    Rt = jnp.swapaxes(R, -1, -2)
    ti = -jnp.einsum("...ij,...j->...i", Rt, t)
    top = jnp.concatenate([Rt, ti[..., None]], axis=-1)
    bottom = jnp.broadcast_to(
        jnp.array([0.0, 0.0, 0.0, 1.0], dtype=T.dtype), top.shape[:-2] + (1, 4)
    )
    return jnp.concatenate([top, bottom], axis=-2)


def transform_points(T: jnp.ndarray, pts: jnp.ndarray) -> jnp.ndarray:
    """Apply (4,4) transform to (...,3) points."""
    R, t = T[..., :3, :3], T[..., :3, 3]
    return jnp.einsum("ij,...j->...i", R, pts) + t


def f32_jit(fn, **jit_kwargs):
    """``jax.jit`` of ``fn`` traced with float32 matmuls.

    Poses, projections and the oracle's blends are small f32 products; at a
    TPU's default precision an f32 dot is one bf16 pass (an 8-bit mantissa:
    about 1 px at 640 px for a point 4 m away).  XLA:CPU computes f32 dots
    in f32 either way, so this changes nothing there."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return jax.jit(traced, **jit_kwargs)
