"""Scene generator ``desk``: a cluttered desk corner with a skewed per-tile load.

A scene module is found by name (``bench/scenes/<kind>.py``, the scene's
name without its trailing digits) and gives ``points(key, n)``: ``n``
points and their colours, drawn from ``key`` on the device."""

import jax
import jax.numpy as jnp


def points(key, n: int):
    """A cluttered corner: three tight blobs in the lower-left foreground
    hold 3/4 of the Gaussians, over a sparse wall and floor, so the per-tile
    load is heavily skewed."""
    ks = jax.random.split(key, 8)
    nw = n // 8
    xy = jax.random.uniform(ks[0], (nw, 2), minval=-2.0, maxval=2.0)
    wall = jnp.stack([xy[:, 0], xy[:, 1] * 0.75, jnp.full((nw,), 4.0)], -1)
    wall_c = jnp.stack([jnp.full((nw,), 0.55), 0.55 + 0.1 * xy[:, 1],
                        jnp.full((nw,), 0.6)], -1)
    xz = jax.random.uniform(ks[1], (nw, 2), minval=jnp.array([-2.0, 1.0]),
                            maxval=jnp.array([2.0, 4.0]))
    floor = jnp.stack([xz[:, 0], jnp.full((nw,), 1.5), xz[:, 1]], -1)
    floor_c = jnp.stack([0.35 + 0.1 * xz[:, 0], jnp.full((nw,), 0.3),
                         jnp.full((nw,), 0.25)], -1)
    blobs = [((-1.05, 1.15, 2.25), 0.18, (0.85, 0.35, 0.2)),
             ((-0.7, 0.85, 2.5), 0.16, (0.25, 0.7, 0.35)),
             ((-1.15, 0.7, 2.1), 0.14, (0.3, 0.4, 0.85))]
    nc = n - 2 * nw
    parts, cols = [wall, floor], [wall_c, floor_c]
    for i, (c, sig, base) in enumerate(blobs):
        m = nc - (nc // 3) * 2 if i == 0 else nc // 3
        p = jnp.array(c) + sig * jax.random.normal(jax.random.fold_in(ks[2], i), (m, 3))
        stripes = jnp.floor((p[:, 0] + p[:, 1]) * 8) % 2
        parts.append(p)
        cols.append(jnp.array(base)[None, :] * (0.55 + 0.45 * stripes[:, None]))
    return jnp.concatenate(parts), jnp.concatenate(cols)
