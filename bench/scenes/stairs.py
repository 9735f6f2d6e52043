"""Scene generator ``stairs``: a staircase climbing away from the camera.

A scene module is found by name (``bench/scenes/<kind>.py``, the scene's
name without its trailing digits) and gives ``points(key, n)``: ``n``
points and their colours, drawn from ``key`` on the device."""

import jax
import jax.numpy as jnp
import numpy as np


def points(key, n: int):
    """Six treads and risers climbing away (the nearest holds ~9x the
    farthest's Gaussians) below a sparse landing wall at z = 5."""
    ks = jax.random.split(key, 4)
    nw = n // 8
    ns = n - nw
    w = np.array([(6 - k) ** 2 for k in range(6)], np.float64)
    counts = np.floor(ns * w / w.sum()).astype(int)
    counts[0] += ns - int(counts.sum())
    parts, cols = [], []
    for k in range(6):
        m = int(counts[k])
        u = jax.random.uniform(jax.random.fold_in(ks[0], k), (m, 2))
        z0, y0 = 1.5 + 0.55 * k, 1.5 - 0.28 * k
        mt = m // 2
        tread = jnp.stack([(u[:mt, 0] - 0.5) * 3.2, jnp.full((mt,), y0),
                           z0 + u[:mt, 1] * 0.55], -1)
        riser = jnp.stack([(u[mt:, 0] - 0.5) * 3.2, y0 + u[mt:, 1] * 0.28,
                           jnp.full((m - mt,), z0)], -1)
        p = jnp.concatenate([tread, riser])
        stripes = jnp.floor(p[:, 0] * 4) % 2
        shade = 0.35 + 0.09 * k
        parts.append(p)
        cols.append(jnp.stack([shade + 0.25 * stripes, jnp.full((m,), 0.3 + 0.05 * k),
                               jnp.full((m,), 0.65 - 0.06 * k)], -1))
    xy = jax.random.uniform(ks[1], (nw, 2), minval=-2.0, maxval=2.0)
    parts.append(jnp.stack([xy[:, 0] * 0.8, xy[:, 1] * 0.6 - 0.4,
                            jnp.full((nw,), 5.0)], -1))
    cols.append(jnp.stack([jnp.full((nw,), 0.6), 0.5 + 0.1 * xy[:, 1],
                           jnp.full((nw,), 0.45)], -1))
    pts = jnp.concatenate(parts)
    return pts + 0.008 * jax.random.normal(ks[2], pts.shape), jnp.concatenate(cols)
