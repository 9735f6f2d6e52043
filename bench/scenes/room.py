"""Scene generator ``room``: a room corner with two boxes.

A scene module is found by name (``bench/scenes/<kind>.py``, the scene's
name without its trailing digits) and gives ``points(key, n)``: ``n``
points and their colours, drawn from ``key`` on the device."""

import jax
import jax.numpy as jnp


def _box(k, n, center, size, base_col):
    u = jax.random.uniform(k, (n, 3), minval=-1.0, maxval=1.0)
    face = jax.random.randint(jax.random.fold_in(k, 1), (n,), 0, 3)
    sign = jax.random.randint(jax.random.fold_in(k, 2), (n,), 0, 2) * 2 - 1
    pts = (u * size).at[jnp.arange(n), face].set(sign * jnp.take(size, face))
    stripes = jnp.floor((u[:, 0] + u[:, 1]) * 3) % 2
    return pts + center, base_col[None, :] * (0.6 + 0.4 * stripes[:, None])


def points(key, n: int):
    """Checkered back wall (z = 4), graded floor (y = 1.5), two striped
    boxes, 1 cm of jitter."""
    ks = jax.random.split(key, 8)
    q = n // 4
    xy = jax.random.uniform(ks[0], (q, 2), minval=-2.0, maxval=2.0)
    wall = jnp.stack([xy[:, 0], xy[:, 1] * 0.75, jnp.full((q,), 4.0)], -1)
    check = (jnp.floor(xy[:, 0] * 2) + jnp.floor(xy[:, 1] * 2)) % 2
    wall_c = jnp.stack([0.2 + 0.6 * check, 0.3 + 0.2 * check, 0.8 - 0.5 * check], -1)
    xz = jax.random.uniform(ks[1], (q, 2), minval=jnp.array([-2.0, 1.0]),
                            maxval=jnp.array([2.0, 4.0]))
    floor = jnp.stack([xz[:, 0], jnp.full((q,), 1.5), xz[:, 1]], -1)
    floor_c = jnp.stack([0.4 + 0.15 * xz[:, 0], jnp.full((q,), 0.35),
                         0.2 + 0.2 * (xz[:, 1] - 1) / 3], -1)
    nb = n - 2 * q
    b1, c1 = _box(ks[2], nb // 2, jnp.array([-0.8, 1.1, 2.8]),
                  jnp.array([0.35, 0.4, 0.35]), jnp.array([0.9, 0.5, 0.2]))
    b2, c2 = _box(ks[3], nb - nb // 2, jnp.array([0.9, 1.0, 3.2]),
                  jnp.array([0.3, 0.5, 0.3]), jnp.array([0.3, 0.8, 0.4]))
    pts = jnp.concatenate([wall, floor, b1, b2])
    cols = jnp.concatenate([wall_c, floor_c, c1, c2])
    return pts + 0.01 * jax.random.normal(ks[4], pts.shape), cols
