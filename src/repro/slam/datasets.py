"""Synthetic RGB-D SLAM datasets (no TUM/Replica offline in this container).

A ground-truth Gaussian field — a procedural "room" (back wall, floor, side
walls, textured boxes) — is rendered along a smooth SE(3) trajectory by our
own forward renderer, producing RGB + depth frames plus the ground-truth
trajectory. SLAM then re-localizes and re-maps from scratch; ATE/PSNR are
measured exactly as the paper measures them on TUM/Replica.

Scenes are deterministic in (name, seed): 'room0', 'room1', 'hall0' mimic
the paper's multi-scene evaluation; 'desk0' is a cluttered close-range
corner whose per-tile fragment load is heavily skewed (most geometry piles
into a few tiles while the walls stay sparse) — the workload shape the WSU's
pairwise scheduling exists for, and what real TUM/Replica frames look like.
'stairs0' is a staircase receding from the camera: most of the geometry
crowds the near treads at the bottom of the image while the upper half is
a sparse distant landing — strong depth AND occupancy skew, so a sharded
serving pool mixing it with room scenes exercises genuinely heterogeneous
per-row workloads.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gaussians as G
from repro.core.camera import Camera, Intrinsics, look_at
from repro.core.lie import f32_jit
from repro.core.raster_api import RasterPlan
from repro.core.render import render
from repro.core.sorting import make_tile_grid


@dataclasses.dataclass
class Frame:
    rgb: np.ndarray      # (H, W, 3) float32 in [0,1]
    depth: np.ndarray    # (H, W) float32, 0 = invalid
    w2c_gt: np.ndarray   # (4, 4) ground-truth pose


@dataclasses.dataclass
class SLAMDataset:
    name: str
    intrinsics: Intrinsics
    frames: List[Frame]
    gt_field: G.GaussianField

    @property
    def num_frames(self) -> int:
        return len(self.frames)


def _desk_points(key, n: int):
    """'desk0': a cluttered corner — ~3/4 of the geometry piles into a few
    small objects close to the camera while the wall/floor stay sparse.
    Per-tile fragment counts end up heavily skewed (tail ratio ~2.5-3 vs
    ~1.7 for the uniform rooms), which is the distribution the WSU's
    pairwise scheduling is designed to flatten."""
    ks = jax.random.split(key, 8)
    n_wall = n // 8
    n_floor = n // 8
    n_clutter = n - n_wall - n_floor

    # Sparse back wall (z = 4), dim wash.
    xy = jax.random.uniform(ks[0], (n_wall, 2), minval=-2.0, maxval=2.0)
    wall = jnp.stack([xy[:, 0], xy[:, 1] * 0.75, jnp.full((n_wall,), 4.0)], -1)
    wall_col = jnp.stack([jnp.full((n_wall,), 0.55), 0.55 + 0.1 * xy[:, 1],
                          jnp.full((n_wall,), 0.6)], -1)

    # Sparse floor (y = 1.5).
    xz = jax.random.uniform(ks[1], (n_floor, 2), minval=jnp.array([-2.0, 1.0]),
                            maxval=jnp.array([2.0, 4.0]))
    floor = jnp.stack([xz[:, 0], jnp.full((n_floor,), 1.5), xz[:, 1]], -1)
    floor_col = jnp.stack([0.35 + 0.1 * xz[:, 0], jnp.full((n_floor,), 0.3),
                           jnp.full((n_floor,), 0.25)], -1)

    # Dense clutter: three tight blobs stacked in the lower-left foreground.
    blob_specs = [
        (jnp.array([-1.05, 1.15, 2.25]), 0.18, jnp.array([0.85, 0.35, 0.2])),
        (jnp.array([-0.7, 0.85, 2.5]), 0.16, jnp.array([0.25, 0.7, 0.35])),
        (jnp.array([-1.15, 0.7, 2.1]), 0.14, jnp.array([0.3, 0.4, 0.85])),
    ]
    blobs, blob_cols = [], []
    per = n_clutter // len(blob_specs)
    for i, (center, sigma, base) in enumerate(blob_specs):
        m = n_clutter - per * (len(blob_specs) - 1) if i == 0 else per
        p = center + sigma * jax.random.normal(jax.random.fold_in(ks[2], i), (m, 3))
        stripes = (jnp.floor((p[:, 0] + p[:, 1]) * 8) % 2)
        blobs.append(p)
        blob_cols.append(base[None, :] * (0.55 + 0.45 * stripes[:, None]))

    pts = jnp.concatenate([wall, floor] + blobs, axis=0)
    cols = jnp.concatenate([wall_col, floor_col] + blob_cols, axis=0)
    return pts, jnp.clip(cols, 0.02, 0.98)


def _stairs_points(key, n: int):
    """'stairs0': a staircase climbing away from the camera.  Geometry is
    allocated quadratically toward the near steps (the bottom tread gets
    ~9x the top one), with a sparse landing wall far behind — per-tile
    occupancy piles into the lower image rows and depth spans ~1.5-5m in
    one view, the strongest depth/occupancy skew of the registry."""
    ks = jax.random.split(key, 4)
    n_wall = n // 8
    n_steps = n - n_wall
    k_steps = 6

    # Quadratic near-step bias: step k (0 = nearest) gets ~(K-k)^2 weight.
    w = np.array([(k_steps - k) ** 2 for k in range(k_steps)], np.float64)
    counts = np.floor(n_steps * w / w.sum()).astype(int)
    counts[0] += n_steps - int(counts.sum())

    pts_parts, col_parts = [], []
    for k in range(k_steps):
        m = int(counts[k])
        kk = jax.random.fold_in(ks[0], k)
        u = jax.random.uniform(kk, (m, 2), minval=0.0, maxval=1.0)
        z0, y0 = 1.5 + 0.55 * k, 1.5 - 0.28 * k
        # Half tread (horizontal, y = y0), half riser (vertical, z = z0).
        m_t = m // 2
        tread = jnp.stack([(u[:m_t, 0] - 0.5) * 3.2,
                           jnp.full((m_t,), y0),
                           z0 + u[:m_t, 1] * 0.55], -1)
        riser = jnp.stack([(u[m_t:, 0] - 0.5) * 3.2,
                           y0 + u[m_t:, 1] * 0.28,
                           jnp.full((m - m_t,), z0)], -1)
        p = jnp.concatenate([tread, riser], 0)
        stripes = (jnp.floor(p[:, 0] * 4) % 2)
        shade = 0.35 + 0.09 * k
        col = jnp.stack([shade + 0.25 * stripes,
                         jnp.full((m,), 0.3 + 0.05 * k),
                         jnp.full((m,), 0.65 - 0.06 * k)], -1)
        pts_parts.append(p)
        col_parts.append(col)

    # Sparse landing wall behind the top step.
    xy = jax.random.uniform(ks[1], (n_wall, 2), minval=-2.0, maxval=2.0)
    wall = jnp.stack([xy[:, 0] * 0.8, xy[:, 1] * 0.6 - 0.4,
                      jnp.full((n_wall,), 5.0)], -1)
    wall_col = jnp.stack([jnp.full((n_wall,), 0.6),
                          0.5 + 0.1 * xy[:, 1],
                          jnp.full((n_wall,), 0.45)], -1)

    pts = jnp.concatenate(pts_parts + [wall], axis=0)
    cols = jnp.concatenate(col_parts + [wall_col], axis=0)
    noise = 0.008 * jax.random.normal(ks[2], pts.shape)
    return pts + noise, jnp.clip(cols, 0.02, 0.98)


def _corridor_points(key, n: int):
    """'corridor0': a long straight corridor (z in [1, 13]) of repeated
    geometry — floor, two side walls, and a pillar pair every ~2m.  The
    camera *translates through* it (see ``_trajectory``), so early geometry
    leaves the frustum permanently: at any time only a short z-slice of the
    map is visible.  This is the PagedMap workload — a flat map sweeps all
    of it every fragment build, a paged map only the visible pages."""
    ks = jax.random.split(key, 6)
    z0, z1 = 1.0, 13.0
    n_floor = n // 4
    n_wall = n // 4
    n_pillar = n - n_floor - 2 * n_wall

    # Floor (y = 1.5), z-striped so repeated sections stay distinguishable.
    xz = jax.random.uniform(ks[0], (n_floor, 2),
                            minval=jnp.array([-1.5, z0]),
                            maxval=jnp.array([1.5, z1]))
    floor = jnp.stack([xz[:, 0], jnp.full((n_floor,), 1.5), xz[:, 1]], -1)
    fstripe = (jnp.floor(xz[:, 1] * 1.5) % 2)
    floor_col = jnp.stack([0.3 + 0.2 * fstripe,
                           jnp.full((n_floor,), 0.32),
                           0.25 + 0.1 * (xz[:, 1] - z0) / (z1 - z0)], -1)

    # Side walls (x = +/-1.5), checkered in (y, z).
    def wall(k, x_side):
        yz = jax.random.uniform(k, (n_wall, 2),
                                minval=jnp.array([-0.6, z0]),
                                maxval=jnp.array([1.5, z1]))
        p = jnp.stack([jnp.full((n_wall,), x_side), yz[:, 0], yz[:, 1]], -1)
        check = ((jnp.floor(yz[:, 0] * 2) + jnp.floor(yz[:, 1] * 1.2)) % 2)
        col = jnp.stack([0.25 + 0.5 * check,
                         0.35 + 0.15 * check,
                         0.7 - 0.4 * check * (0.5 + x_side / 3.0)], -1)
        return p, col

    wl, wl_col = wall(ks[1], -1.5)
    wr, wr_col = wall(ks[2], 1.5)

    # Pillar pairs every 2m — the repeated landmark structure.
    n_pairs = 6
    per = n_pillar // n_pairs
    pil_parts, pil_cols = [], []
    for i in range(n_pairs):
        m = n_pillar - per * (n_pairs - 1) if i == 0 else per
        kk = jax.random.fold_in(ks[3], i)
        u = jax.random.normal(kk, (m, 3)) * jnp.array([0.12, 0.45, 0.12])
        side = 1.0 if i % 2 == 0 else -1.0
        center = jnp.array([side * 1.0, 0.7, z0 + 1.0 + 2.0 * i])
        p = u + center
        hue = i / max(n_pairs - 1, 1)
        col = jnp.stack([jnp.full((m,), 0.85 - 0.5 * hue),
                         jnp.full((m,), 0.3 + 0.5 * hue),
                         jnp.full((m,), 0.35)], -1)
        pil_parts.append(p)
        pil_cols.append(col)

    pts = jnp.concatenate([floor, wl, wr] + pil_parts, axis=0)
    cols = jnp.concatenate([floor_col, wl_col, wr_col] + pil_cols, axis=0)
    noise = 0.008 * jax.random.normal(ks[4], pts.shape)
    return pts + noise, jnp.clip(cols, 0.02, 0.98)


# Registered synthetic scenes (mirrors the raster backend registry's error
# style: unknown names raise listing what exists instead of a bare KeyError
# or a silent fallback to room0's geometry).
SCENES: tuple = ("room0", "room1", "hall0", "desk0", "stairs0", "corridor0")


def registered_scenes() -> tuple:
    return SCENES


def _surface_points(key, name: str, n: int):
    """Sample points + colors on a procedural room's surfaces."""
    if name.startswith("desk"):
        return _desk_points(key, n)
    if name.startswith("stairs"):
        return _stairs_points(key, n)
    if name.startswith("corridor"):
        return _corridor_points(key, n)
    ks = jax.random.split(key, 8)
    quarters = n // 4

    # Back wall (z = 4), checkered texture.
    xy = jax.random.uniform(ks[0], (quarters, 2), minval=-2.0, maxval=2.0)
    wall = jnp.stack([xy[:, 0], xy[:, 1] * 0.75, jnp.full((quarters,), 4.0)], -1)
    check = ((jnp.floor(xy[:, 0] * 2) + jnp.floor(xy[:, 1] * 2)) % 2)
    wall_col = jnp.stack([0.2 + 0.6 * check, 0.3 + 0.2 * check, 0.8 - 0.5 * check], -1)

    # Floor (y = 1.5), gradient texture.
    xz = jax.random.uniform(ks[1], (quarters, 2), minval=jnp.array([-2.0, 1.0]),
                            maxval=jnp.array([2.0, 4.0]))
    floor = jnp.stack([xz[:, 0], jnp.full((quarters,), 1.5), xz[:, 1]], -1)
    floor_col = jnp.stack(
        [0.4 + 0.15 * xz[:, 0], jnp.full((quarters,), 0.35), 0.2 + 0.2 * (xz[:, 1] - 1) / 3],
        -1,
    )

    # Two textured boxes in the middle of the scene.
    def box(k, center, size, base_col):
        u = jax.random.uniform(k, (quarters // 2, 3), minval=-1.0, maxval=1.0)
        face = jax.random.randint(jax.random.fold_in(k, 1), (quarters // 2,), 0, 3)
        sign = jax.random.randint(jax.random.fold_in(k, 2), (quarters // 2,), 0, 2) * 2 - 1
        pts = u * size
        pts = pts.at[jnp.arange(quarters // 2), face].set(sign * size[face] if False else sign * jnp.take(size, face))
        stripes = (jnp.floor((u[:, 0] + u[:, 1]) * 3) % 2)
        col = base_col[None, :] * (0.6 + 0.4 * stripes[:, None])
        return pts + center, col

    b1, c1 = box(ks[2], jnp.array([-0.8, 1.1, 2.8]), jnp.array([0.35, 0.4, 0.35]),
                 jnp.array([0.9, 0.5, 0.2]))
    b2, c2 = box(ks[3], jnp.array([0.9, 1.0, 3.2]), jnp.array([0.3, 0.5, 0.3]),
                 jnp.array([0.3, 0.8, 0.4]))

    pts = jnp.concatenate([wall, floor, b1, b2], axis=0)
    cols = jnp.concatenate([wall_col, floor_col, c1, c2], axis=0)
    # Scene variants jitter geometry deterministically.
    offset = {"room0": 0.0, "room1": 0.35, "hall0": -0.3}.get(name, 0.0)
    pts = pts + jnp.array([offset, 0.0, offset * 0.5])
    noise = 0.01 * jax.random.normal(ks[4], pts.shape)
    return pts + noise, jnp.clip(cols, 0.02, 0.98)


def _trajectory(name: str, num_frames: int):
    """Smooth arc orbiting the scene center, with mild vertical bobbing.
    'corridor0' instead translates straight down the corridor (z 0 -> 4,
    looking ahead): geometry behind the camera leaves the frustum for good,
    which is what makes its late-trajectory visible set small."""
    ts = np.linspace(0.0, 1.0, num_frames)
    poses = []
    if name.startswith("corridor"):
        for t in ts:
            # Ease-in (z ~ t^2): the per-frame step grows from ~0 to its
            # maximum, so the constant-velocity motion model can bootstrap
            # — the tracker only ever corrects the step-to-step residual,
            # never an absolute 0.7 m jump from a standing start.
            z = 4.0 * t * t
            eye = np.array([0.2 * np.sin(3.0 * t), 0.45 + 0.05 * np.sin(5.0 * t), z])
            target = np.array([0.1 * np.sin(3.0 * t + 0.5), 0.6, z + 3.0])
            w2c = look_at(jnp.asarray(eye, jnp.float32),
                          jnp.asarray(target, jnp.float32),
                          jnp.asarray([0.0, -1.0, 0.0], jnp.float32))
            poses.append(np.asarray(w2c))
        return poses
    for t in ts:
        ang = (t - 0.5) * {"room0": 0.9, "room1": 1.2, "hall0": 0.7}.get(name, 0.9)
        eye = np.array([1.4 * np.sin(ang), 0.25 * np.sin(2.2 * ang), 0.9 - 0.9 * np.cos(ang)])
        target = np.array([0.4 * np.sin(ang * 0.5), 0.5, 3.0])
        w2c = look_at(jnp.asarray(eye, jnp.float32), jnp.asarray(target, jnp.float32),
                      jnp.asarray([0.0, -1.0, 0.0], jnp.float32))
        poses.append(np.asarray(w2c))
    return poses


def make_dataset(
    name: str = "room0",
    num_frames: int = 40,
    height: int = 96,
    width: int = 128,
    num_gaussians: int = 4096,
    seed: int = 0,
    frag_capacity: int = 128,
) -> SLAMDataset:
    if name not in SCENES:
        raise ValueError(
            f"unknown scene {name!r}; registered scenes: "
            f"{', '.join(SCENES)}"
        )
    # zlib.crc32, not hash(): str hashing is salted per process, which would
    # silently give every process a different "deterministic" scene.
    key = jax.random.PRNGKey(seed + zlib.crc32(name.encode()) % 1000)
    pts, cols = _surface_points(key, name, num_gaussians)
    gt = G.from_points(pts, cols, capacity=num_gaussians, scale=0.045, opacity=0.85)

    f = 0.9 * width
    intr = Intrinsics(fx=f, fy=f, cx=width / 2, cy=height / 2, width=width, height=height)
    grid = make_tile_grid(height, width)
    plan = RasterPlan(grid=grid, backend="ref", capacity=frag_capacity)

    @f32_jit
    def render_frame(w2c):
        out = render(gt, Camera(intr, w2c), plan)
        depth = jnp.where(out.alpha > 0.5, out.depth / jnp.maximum(out.alpha, 1e-6), 0.0)
        return out.image, depth

    frames = []
    for w2c in _trajectory(name, num_frames):
        rgb, depth = render_frame(jnp.asarray(w2c))
        frames.append(Frame(rgb=np.asarray(rgb), depth=np.asarray(depth), w2c_gt=w2c))
    return SLAMDataset(name=name, intrinsics=intr, frames=frames, gt_field=gt)
