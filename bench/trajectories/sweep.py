"""Trajectory ``sweep``: a periodic sinusoidal sweep, replayed cyclically.

A trajectory module is found by name (``bench/trajectories/<generator>.py``,
the ``generator`` of the workload's ``trajectory`` object) and gives
``poses(traj)``: one period of (P, 4, 4) world-to-camera poses, from the
numbers of that object alone."""

import math

import numpy as np

from bench.traffic import look_at


def poses(traj: dict) -> np.ndarray:
    """(P, 4, 4) poses of one period of the periodic sinusoidal sweep: a
    sideways sway with a yaw swing in phase, vertical bob at twice the rate,
    a fixed downward pitch, looking ``distance`` m ahead."""
    p = traj["period_frames"]
    out = []
    for j in range(p):
        ph = 2.0 * math.pi * j / p
        eye = (traj["sway_m"] * math.sin(ph), traj["bob_m"] * math.sin(2 * ph), 0.0)
        yaw = math.radians(traj["yaw_deg"]) * math.sin(ph)
        pitch = math.radians(traj["pitch_deg"])
        d = traj["distance_m"]
        target = (eye[0] + d * math.sin(yaw) * math.cos(pitch),
                  eye[1] + d * math.sin(pitch),
                  eye[2] + d * math.cos(yaw) * math.cos(pitch))
        out.append(look_at(eye, target))
    return np.stack(out)
