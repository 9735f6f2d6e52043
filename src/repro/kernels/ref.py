"""Pure-jnp oracle for the tile rasterizer (forward + autodiff backward).

Defines the *exact* blending semantics that the Pallas kernels mirror:

  1. alpha_k = o_k * exp(-0.5 * d^T conic d), zeroed below ALPHA_MIN,
     clipped at ALPHA_MAX, zeroed for padded fragments.
  2. Texc_k  = prod_{j<k} (1 - alpha_j)            (exclusive transmittance)
  3. include_k = Texc_k > TERM_EPS                 (early termination; a
     prefix property because Texc is non-increasing)
  4. w_k     = Texc_k * alpha_k * include_k        (blend weight)
  5. color   = sum_k w_k c_k ; depth = sum_k w_k d_k ;
     final_T = prod_k (1 - alpha_k * include_k)

Everything is differentiable jnp, so ``jax.grad`` through this module is the
reference for the hand-derived Pallas backward. Memory is O(tiles * 256 * K)
— fine for test-sized scenes, which is all the oracle is for.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sorting import TILE, TileGrid

ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
TERM_EPS = 1e-4

NUM_ATTRS = 12  # packed attribute rows, see sorting.gather_tile_attributes
PIX = TILE * TILE


def tile_pixel_coords(grid: TileGrid) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pixel-center coordinates per tile: two (num_tiles, 256) arrays (x, y)."""
    ty, tx = jnp.meshgrid(
        jnp.arange(grid.grid_h, dtype=jnp.float32),
        jnp.arange(grid.grid_w, dtype=jnp.float32),
        indexing="ij",
    )
    py, px = jnp.meshgrid(
        jnp.arange(TILE, dtype=jnp.float32),
        jnp.arange(TILE, dtype=jnp.float32),
        indexing="ij",
    )
    x = (tx.reshape(-1, 1) * TILE + px.reshape(1, -1)) + 0.5
    y = (ty.reshape(-1, 1) * TILE + py.reshape(1, -1)) + 0.5
    return x, y  # each (T, 256)


def fragment_alphas(attrs: jnp.ndarray, grid: TileGrid) -> jnp.ndarray:
    """Alpha of every fragment: (T, 256, K). Step 3-1 'Alpha Computing'."""
    px, py = tile_pixel_coords(grid)  # (T, 256)
    mu_x, mu_y = attrs[:, 0], attrs[:, 1]            # (T, K)
    ca, cb, cc = attrs[:, 2], attrs[:, 3], attrs[:, 4]
    o = attrs[:, 8]
    present = attrs[:, 10] > 0.5

    dx = px[:, :, None] - mu_x[:, None, :]           # (T, 256, K)
    dy = py[:, :, None] - mu_y[:, None, :]
    q = (
        ca[:, None, :] * dx * dx
        + 2.0 * cb[:, None, :] * dx * dy
        + cc[:, None, :] * dy * dy
    )
    gauss = jnp.exp(-0.5 * jnp.maximum(q, 0.0))
    alpha = jnp.minimum(o[:, None, :] * gauss, ALPHA_MAX)
    alpha = jnp.where((alpha >= ALPHA_MIN) & present[:, None, :], alpha, 0.0)
    return alpha


def blend(attrs: jnp.ndarray, alpha: jnp.ndarray):
    """Step 3-2 'Alpha Blending' with early termination. Returns
    (color (T,256,3), depth (T,256), final_T (T,256))."""
    texc = jnp.cumprod(1.0 - alpha, axis=-1)
    texc = jnp.concatenate([jnp.ones_like(texc[..., :1]), texc[..., :-1]], axis=-1)
    include = texc > TERM_EPS
    w = texc * alpha * include  # (T,256,K)

    rgb = attrs[:, 5:8]         # (T,3,K)
    # The oracle's sums stay f32 on every backend (a TPU's default f32
    # einsum is one bf16 pass).
    hi = jax.lax.Precision.HIGHEST
    color = jnp.einsum("tpk,tck->tpc", w, rgb, precision=hi)
    depth = jnp.einsum("tpk,tk->tp", w, attrs[:, 9], precision=hi)
    final_t = jnp.prod(1.0 - alpha * include, axis=-1)
    return color, depth, final_t


def rasterize_tiles(attrs: jnp.ndarray, grid: TileGrid):
    """Full per-tile rasterization from packed attrs (T, 12, K)."""
    alpha = fragment_alphas(attrs, grid)
    return blend(attrs, alpha)


def tiles_to_image(tiled: jnp.ndarray, grid: TileGrid) -> jnp.ndarray:
    """(T, 256, C?) tile-major -> (H, W, C?) image."""
    chan = tiled.shape[2:] if tiled.ndim > 2 else ()
    x = tiled.reshape((grid.grid_h, grid.grid_w, TILE, TILE) + chan)
    x = jnp.swapaxes(x, 1, 2)
    return x.reshape((grid.height, grid.width) + chan)


def image_to_tiles(img: jnp.ndarray, grid: TileGrid) -> jnp.ndarray:
    """(H, W, C?) -> (T, 256, C?)."""
    chan = img.shape[2:] if img.ndim > 2 else ()
    x = img.reshape((grid.grid_h, TILE, grid.grid_w, TILE) + chan)
    x = jnp.swapaxes(x, 1, 2)
    return x.reshape((grid.num_tiles, PIX) + chan)


def rasterize_image(attrs: jnp.ndarray, grid: TileGrid):
    """Convenience: packed attrs -> (H,W,3) premultiplied color, (H,W) depth,
    (H,W) final transmittance."""
    color, depth, final_t = rasterize_tiles(attrs, grid)
    return (
        tiles_to_image(color, grid),
        tiles_to_image(depth, grid),
        tiles_to_image(final_t, grid),
    )
