"""SLAM optimization objective (Eq. 6) and image-quality metrics.

L = lambda_pho * E_pho + (1 - lambda_pho) * E_geo — photometric + geometric
residuals between rendered and observed RGB-D. The §4.1 pruning score reuses
the gradients of exactly this loss (no extra loss terms are introduced —
that is the paper's "no overhead" property).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.obs.profiling import scoped


@scoped("loss")
def slam_loss(
    rendered_rgb: jnp.ndarray,   # (H, W, 3)
    rendered_depth: jnp.ndarray,  # (H, W) premultiplied by alpha
    rendered_alpha: jnp.ndarray,  # (H, W)
    obs_rgb: jnp.ndarray,
    obs_depth: jnp.ndarray,
    lambda_pho: float = 0.9,
    depth_valid_min: float = 1e-3,
) -> jnp.ndarray:
    e_pho = jnp.mean(jnp.abs(rendered_rgb - obs_rgb))
    # Geometric residual only where both observation and rendering cover.
    mask = (obs_depth > depth_valid_min) & (rendered_alpha > 0.5)
    # Rendered depth is alpha-premultiplied; normalize where covered.
    norm_depth = rendered_depth / jnp.maximum(rendered_alpha, 1e-6)
    e_geo = jnp.sum(jnp.abs(norm_depth - obs_depth) * mask) / jnp.maximum(
        jnp.sum(mask), 1.0
    )
    return lambda_pho * e_pho + (1.0 - lambda_pho) * e_geo


@scoped("loss")
def psnr(a: jnp.ndarray, b: jnp.ndarray, max_val: float = 1.0) -> jnp.ndarray:
    mse = jnp.mean((a - b) ** 2)
    return 10.0 * jnp.log10(max_val**2 / jnp.maximum(mse, 1e-12))


def rmse(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.mean((a - b) ** 2))


def ssim(a: jnp.ndarray, b: jnp.ndarray, window: int = 8) -> jnp.ndarray:
    """Coarse block SSIM (paper Fig. 5 uses SSIM for frame-similarity)."""
    c1, c2 = 0.01**2, 0.03**2

    def blocks(x):
        h, w = x.shape[0] // window * window, x.shape[1] // window * window
        x = x[:h, :w]
        if x.ndim == 3:
            x = jnp.mean(x, axis=-1)
        return x.reshape(h // window, window, w // window, window).transpose(0, 2, 1, 3)

    ba, bb = blocks(a), blocks(b)
    mu_a = ba.mean(axis=(-1, -2))
    mu_b = bb.mean(axis=(-1, -2))
    var_a = ba.var(axis=(-1, -2))
    var_b = bb.var(axis=(-1, -2))
    cov = ((ba - mu_a[..., None, None]) * (bb - mu_b[..., None, None])).mean(axis=(-1, -2))
    s = ((2 * mu_a * mu_b + c1) * (2 * cov + c2)) / (
        (mu_a**2 + mu_b**2 + c1) * (var_a + var_b + c2)
    )
    return jnp.mean(s)
