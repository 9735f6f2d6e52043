"""The 3D Gaussian scene representation (Eq. 1 of the paper).

``GaussianField`` is a fixed-capacity structure-of-arrays pytree. XLA needs
static shapes, so SLAM "adds"/"removes" Gaussians by toggling an ``alive``
mask and periodically compacting (alive entries sorted to the front). This is
the TPU-native equivalent of the paper's dynamic Gaussian pool, and the
mask doubles as the §4.1 *mask-prune* state: masked Gaussians are excluded
from rendering for K iterations before being permanently removed.

Parameterization (standard 3DGS):
  mu        (N,3)  position
  log_scale (N,3)  anisotropic scale (exp -> positive)
  quat      (N,4)  rotation (normalized on use)
  logit_o   (N,)   opacity (sigmoid -> (0,1))
  color     (N,3)  RGB in [0,1] via sigmoid (SH degree 0; SLAM pipelines
                   like MonoGS track RGB only, which we follow)
"""

from __future__ import annotations

import math

from typing import NamedTuple

import jax
import jax.numpy as jnp


def dot3(a, b) -> jnp.ndarray:
    """Dot product of two 3-vectors given as triples of (N,) components."""
    return a[0] * b[0] + a[1] * b[1] + a[2] * b[2]


class GaussianField(NamedTuple):
    mu: jnp.ndarray        # (N, 3) float32
    log_scale: jnp.ndarray  # (N, 3) float32
    quat: jnp.ndarray      # (N, 4) float32
    logit_o: jnp.ndarray   # (N,) float32
    color: jnp.ndarray     # (N, 3) float32 (pre-sigmoid)
    alive: jnp.ndarray     # (N,) bool — capacity mask + §4.1 prune mask

    @property
    def capacity(self) -> int:
        return self.mu.shape[0]

    def num_alive(self) -> jnp.ndarray:
        return jnp.sum(self.alive.astype(jnp.int32))

    def opacity(self) -> jnp.ndarray:
        return jax.nn.sigmoid(self.logit_o)

    def rgb(self) -> jnp.ndarray:
        return jax.nn.sigmoid(self.color)

    def scales(self) -> jnp.ndarray:
        return jnp.exp(self.log_scale)

    def covariance_terms(self) -> tuple[jnp.ndarray, ...]:
        """3D covariance Sigma = (R S)(R S)^T as its six unique entries.

        Returns ``(xx, xy, xz, yy, yz, zz)``, each (N,), with R the rotation
        of the normalised quaternion and S = diag(exp(log_scale)). Written
        out as f32 multiply-adds on per-Gaussian components: a batched
        (N,3,3) matmul would run as multi-pass MXU work on 3x3 tiles.
        """
        w, x, y, z = (self.quat[:, i] for i in range(4))
        n = jnp.sqrt(w * w + x * x + y * y + z * z) + 1e-9
        w, x, y, z = w / n, x / n, y / n, z / n
        s = self.scales()
        sx, sy, sz = s[:, 0], s[:, 1], s[:, 2]
        # Rows of M = R S (column k of R scaled by s_k).
        m0 = ((1 - 2 * (y * y + z * z)) * sx, 2 * (x * y - w * z) * sy, 2 * (x * z + w * y) * sz)
        m1 = (2 * (x * y + w * z) * sx, (1 - 2 * (x * x + z * z)) * sy, 2 * (y * z - w * x) * sz)
        m2 = (2 * (x * z - w * y) * sx, 2 * (y * z + w * x) * sy, (1 - 2 * (x * x + y * y)) * sz)
        return (dot3(m0, m0), dot3(m0, m1), dot3(m0, m2),
                dot3(m1, m1), dot3(m1, m2), dot3(m2, m2))


PARAM_FIELDS = ("mu", "log_scale", "quat", "logit_o", "color")


def params_of(g: GaussianField) -> dict:
    """Trainable float leaves (excludes the bool ``alive`` mask) — the pytree
    SLAM optimizers differentiate with respect to."""
    return {f: getattr(g, f) for f in PARAM_FIELDS}


def with_params(g: GaussianField, params: dict) -> GaussianField:
    return g._replace(**params)


def empty(capacity: int) -> GaussianField:
    return GaussianField(
        mu=jnp.zeros((capacity, 3), jnp.float32),
        log_scale=jnp.full((capacity, 3), -10.0, jnp.float32),
        quat=jnp.tile(jnp.array([1.0, 0.0, 0.0, 0.0], jnp.float32), (capacity, 1)),
        logit_o=jnp.full((capacity,), -10.0, jnp.float32),
        color=jnp.zeros((capacity, 3), jnp.float32),
        alive=jnp.zeros((capacity,), bool),
    )


def from_points(
    points: jnp.ndarray,
    colors: jnp.ndarray,
    capacity: int,
    scale: float = 0.05,
    opacity: float = 0.7,
) -> GaussianField:
    """Seed a field from a point cloud (e.g. back-projected depth map)."""
    n = points.shape[0]
    assert n <= capacity, f"{n} points exceed capacity {capacity}"
    g = empty(capacity)
    inv_sig = jnp.log(jnp.clip(colors, 1e-4, 1 - 1e-4) / (1 - jnp.clip(colors, 1e-4, 1 - 1e-4)))
    logit_op = math.log(opacity / (1 - opacity))
    return g._replace(
        mu=g.mu.at[:n].set(points),
        log_scale=g.log_scale.at[:n].set(jnp.log(scale)),
        logit_o=g.logit_o.at[:n].set(logit_op),
        color=g.color.at[:n].set(inv_sig),
        alive=g.alive.at[:n].set(True),
    )


def compact(g: GaussianField) -> GaussianField:
    """Sort alive Gaussians to the front (the §4.1 'permanent removal').

    Pure data movement; preserves the set of alive Gaussians. Keeps fragment
    list indices dense so per-tile capacity is not wasted on dead entries.
    """
    order = jnp.argsort(~g.alive, stable=True)  # alive (False<True) first
    return GaussianField(
        mu=g.mu[order],
        log_scale=g.log_scale[order],
        quat=g.quat[order],
        logit_o=g.logit_o[order],
        color=g.color[order],
        alive=g.alive[order],
    )


def insert(g: GaussianField, new: GaussianField, max_new: int) -> GaussianField:
    """Insert up to ``max_new`` alive entries of ``new`` into dead slots of ``g``.

    Used by mapping densification. Deterministic: fills the lowest-index dead
    slots with the lowest-index alive entries of ``new``.
    """
    dead_rank = jnp.cumsum((~g.alive).astype(jnp.int32)) - 1  # rank among dead slots
    src_rank = jnp.cumsum(new.alive.astype(jnp.int32)) - 1    # rank among new alive

    # For each destination slot: which source rank would fill it (if any).
    take = jnp.where((~g.alive) & (dead_rank < max_new), dead_rank, -1)  # (N,)
    # Gather source index for each rank.
    src_idx_for_rank = jnp.full((g.capacity,), -1, jnp.int32)
    src_positions = jnp.arange(new.capacity, dtype=jnp.int32)
    valid_src = new.alive & (src_rank < max_new)
    src_idx_for_rank = src_idx_for_rank.at[jnp.where(valid_src, src_rank, g.capacity - 1)].set(
        jnp.where(valid_src, src_positions, -1), mode="drop"
    )
    src_for_slot = jnp.where(take >= 0, src_idx_for_rank[jnp.clip(take, 0, g.capacity - 1)], -1)
    use = src_for_slot >= 0
    sf = jnp.clip(src_for_slot, 0, new.capacity - 1)

    def mix(dst, src):
        picked = src[sf]
        return jnp.where(use.reshape((-1,) + (1,) * (dst.ndim - 1)), picked, dst)

    return GaussianField(
        mu=mix(g.mu, new.mu),
        log_scale=mix(g.log_scale, new.log_scale),
        quat=mix(g.quat, new.quat),
        logit_o=mix(g.logit_o, new.logit_o),
        color=mix(g.color, new.color),
        alive=jnp.where(use, True, g.alive),
    )
