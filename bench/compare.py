"""The comparison that decides ``correct``.

What is compared is what the timed path produced in the run's first
``checked_frames`` frames (the workload's: the warm-up period in set-up,
then the window's own frame-steps, carried on after the window if it closed
sooner): per stream, each frame's pose, every tracking iteration's loss,
and on each keyframe every mapping iteration's loss and the post-mapping
PSNR.  The configuration's plain reference (the module
its ``reference`` key names, ``bench/reference.py``) replays the same
frames from the same seed.  Each number below is a largest gap over
streams and frames; the configuration file holds the limit of each
number that is compared (``correct.limits``), set from chip readings as
``PERF.md`` records.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys

import numpy as np

from bench.spec import ROOT


@functools.lru_cache(maxsize=None)
def _module(path: str):
    name = "bench_reference_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod        # its dataclasses look themselves up here
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _stream(path: str, setup, precision: str):
    return _module(path).Stream(setup, precision)


def reference_stream(cfg: dict, precision: str = "highest"):
    """The configuration's plain reference (the module its ``reference``
    key names), compiled once per (configuration, precision) in a process."""
    mod = _module(cfg["reference"])
    return _stream(cfg["reference"], mod.setup(cfg), precision)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def numbers(program: list, ref: list) -> dict:
    """Gaps between the program's records and the reference's: ``program``
    is per step a list over streams, ``ref`` per stream a list over steps."""
    out = {"pose": 0.0, "pose_first": 0.0, "track_loss": 0.0,
           "track_loss_first": 0.0, "map_loss": 0.0, "map_loss_first": 0.0,
           "psnr_db": 0.0, "keyframes_differ": 0}
    for i, ref_steps in enumerate(ref):
        for n, (step, r) in enumerate(zip(program, ref_steps)):
            p = step[i]
            gap = float(np.max(np.abs(p["pose"] - r["pose"])))
            out["pose"] = max(out["pose"], gap)
            if n == 0:
                out["pose_first"] = max(out["pose_first"], gap)
            out["track_loss"] = max(out["track_loss"],
                                    _rel(p["track_losses"], r["track_losses"]))
            out["track_loss_first"] = max(
                out["track_loss_first"],
                _rel(p["track_losses"][:1], r["track_losses"][:1]))
            if p["is_kf"] != ("psnr" in r):
                out["keyframes_differ"] += 1
            elif p["is_kf"]:
                out["map_loss"] = max(out["map_loss"],
                                      _rel(p["map_losses"], r["map_losses"]))
                out["map_loss_first"] = max(
                    out["map_loss_first"],
                    _rel(p["map_losses"][:1], r["map_losses"][:1]))
                out["psnr_db"] = max(out["psnr_db"], abs(p["psnr"] - r["psnr"]))
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(nums: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and the lines that show each number beside its limit
    (numbers without a limit are shown, marked as not compared)."""
    ok, lines = True, []
    for name, value in nums.items():
        if name in limits:
            passed = value <= limits[name]
            ok &= passed
            lines.append(f"{name} {value!r} limit {limits[name]!r} "
                         f"{'ok' if passed else 'FAILED'}")
        else:
            lines.append(f"{name} {value!r} not compared")
    return bool(ok), lines


def shown(nums: dict, limits: dict) -> dict:
    """The result line's last key: each compared number with its limit."""
    return {k: {"value": v, "limit": limits[k]} for k, v in nums.items()
            if k in limits}
