"""Traffic generator: RGB-D camera streams made on the device from a seed.

A stream is a procedural scene of Gaussians (the ground truth), a periodic
camera path, and the frames the ground truth renders along it.  Every
number comes from the traffic file (``bench/workloads/<cell>.json``) and the
configuration (``bench/configs/<name>.json``); the seed picks only the
scene's random draws, so every seed gives the same sizes, the same camera
path and the same keyframe pattern.

Scenes (``bench/scenes/<kind>.py``) and camera paths
(``bench/trajectories/<generator>.py``) are modules found by name, so a new
scene or path is a new file.  The scenes follow the procedural rooms of the
system's own synthetic data (back wall, floor, textured boxes; a cluttered
desk corner; a staircase), kept here so that a change to the program's data
code cannot move the yardstick.  Frames are rendered with the reference's
plain blend.
"""

from __future__ import annotations

import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import spec


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, including ones wider than 31
    bits (folded in as a second word)."""
    hi, lo = divmod(int(seed), 2 ** 31)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi % 2 ** 31)


def scene_points(name: str, key, n: int):
    """``n`` points and colours of scene ``name`` (``room0`` is drawn by
    ``bench/scenes/room.py``), colours kept off 0 and 1."""
    mod = spec.load_module("scenes", name.rstrip("0123456789"))
    pts, cols = mod.points(key, n)
    return pts, jnp.clip(cols, 0.02, 0.98)


def look_at(eye, target, up=(0.0, -1.0, 0.0)) -> np.ndarray:
    """World-to-camera (4, 4): +z forward, +x right, +y down."""
    eye, target, up = (np.asarray(v, np.float64) for v in (eye, target, up))
    fwd = (target - eye) / np.linalg.norm(target - eye)
    right = np.cross(fwd, up)
    right /= np.linalg.norm(right)
    down = np.cross(fwd, right)
    rot = np.stack([right, down, fwd])
    out = np.eye(4)
    out[:3, :3], out[:3, 3] = rot, -rot @ eye
    return out.astype(np.float32)


def trajectory(traj: dict) -> np.ndarray:
    """One period of poses from the generator the workload names."""
    return spec.load_module("trajectories", traj["generator"]).poses(traj)


@dataclasses.dataclass
class Stream:
    """One camera stream: frames on the device, poses on the host."""

    scene: str
    rgb: list          # P device arrays (H, W, 3) f32
    depth: list        # P device arrays (H, W) f32, 0 = no depth
    poses: np.ndarray  # (P, 4, 4) ground-truth world-to-camera

    def frame(self, i: int):
        """Frame ``i`` of the endless stream: the period replays."""
        j = i % len(self.rgb)
        return self.rgb[j], self.depth[j]


def make_stream(scene: str, seed: int, cfg: dict, traffic: dict) -> Stream:
    """Render one period of ``scene`` (drawn from ``seed``) along the
    workload's sweep, in one jitted call on the default device."""
    gt = traffic["ground_truth"]
    s = R.setup(cfg, k=gt["frag_capacity"])
    poses = trajectory(traffic["trajectory"])
    n = gt["gaussians"]
    key = jax.random.fold_in(seed_key(seed), zlib.crc32(scene.encode()) % 2 ** 31)

    def render_all(key, poses):
        pts, cols = scene_points(scene, key, n)
        f = R.Field(mu=pts, log_scale=jnp.full((n, 3), math.log(gt["scale_m"])),
                    quat=jnp.zeros((n, 4)).at[:, 0].set(1.0),
                    logit_o=jnp.full((n,), math.log(gt["opacity"] / (1 - gt["opacity"]))),
                    color=jnp.log(cols / (1 - cols)), alive=jnp.ones((n,), bool))

        def one(w2c):
            sp = R.project(f, w2c, s)
            img, dep, ft = R.blend(sp, R.tile_lists(sp, s), s)
            alpha = 1.0 - ft
            return img, jnp.where(alpha > 0.5, dep / jnp.maximum(alpha, 1e-6), 0.0)

        return jax.lax.map(one, poses)

    with jax.default_matmul_precision("highest"):
        rgb, depth = jax.jit(render_all)(key, jnp.asarray(poses))
    return Stream(scene=scene, rgb=list(rgb), depth=list(depth), poses=poses)
