"""WSU imbalance telemetry: per-program fragment load before/after pairing,
plus a scheduled-backend engine smoke run.

Two measurements, appended to ``BENCH_slam.json`` under ``"wsu"``, on the
skewed ``desk0`` quick scene (clutter piled into a few tiles — the per-tile
load distribution of real SLAM frames, and the one the WSU targets):

* **imbalance** — per-program fragment load, *provisioned vs streamed*:
  before the WSU every program paid the full max-capacity chunk loop
  (2K fragments per balanced-pair-equivalent of work); the schedule bounds
  each program by its pair's actual load, so max and mean per-program load
  drop >= 2x.  ``tail_*`` tracks the residual balance win of pairing
  (tile-grid max/mean vs pair-grid max/mean; note a pair containing the
  heaviest tile bounds this ratio's reduction at exactly 2x).
* **sched_run** — a short fused ``run_sequence`` with ``backend="schedule"``:
  the schedule rides the scan carries (and the session step), so
  dispatches/syncs per frame must stay at the fused-session floor (~1.0
  dispatch per frame, one finalize sync).

Run:  PYTHONPATH=src python -m benchmarks.run --only wsu
  or: PYTHONPATH=src python -m benchmarks.bench_wsu
"""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

import json
import os
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, stamp
from repro.core.keyframes import KeyframePolicy
from repro.core.schedule import build_schedule, pair_loads
from repro.slam.datasets import make_dataset
from repro.slam.engine import StepEngine
from repro.slam.metrics import imbalance_stats
from repro.slam.session import SLAMConfig, _seed_map, run_sequence


def _imbalance_telemetry(ds, cfg):
    """Per-program fragment-load stats over the scene's tracking lists.

    "Provisioned" is the pre-WSU execution model (every program runs the
    full capacity chunk loop: 2K fragments per pair-of-tiles program);
    "streamed" is what the schedule actually runs (pair loads).  Tile vs
    pair tail ratios isolate the pairing contribution."""
    g = _seed_map(ds, cfg)
    engine = StepEngine(ds.intrinsics, cfg)
    masked = jnp.zeros((cfg.capacity,), bool)
    chunk = engine.stage(1).plan.chunk
    num_tiles = engine.stage(1).grid.num_tiles
    provisioned = 2 * cfg.frag_capacity  # pre-WSU load per pair program
    tile_stats, pair_stats = [], []
    for frame in ds.frames:
        frags = engine.build_lists(g, masked, jnp.asarray(frame.w2c_gt))
        count = np.asarray(frags.count)
        sched = build_schedule(frags.count, chunk,
                               max_trips=cfg.frag_capacity // chunk)
        tile_stats.append(imbalance_stats(count))
        pair_stats.append(imbalance_stats(np.asarray(pair_loads(sched))))

    def mean_stats(rows):
        return {
            "max_load": round(float(np.mean([r.max_load for r in rows])), 2),
            "mean_load": round(float(np.mean([r.mean_load for r in rows])), 2),
            "tail_ratio": round(float(np.mean([r.tail_ratio for r in rows])), 3),
        }

    t, p = mean_stats(tile_stats), mean_stats(pair_stats)
    return {
        "programs": (num_tiles + 1) // 2,
        "provisioned_load_per_program": provisioned,
        "streamed_load_per_program": p,
        "max_load_reduction": round(provisioned / max(p["max_load"], 1e-9), 2),
        "mean_load_reduction": round(provisioned / max(p["mean_load"], 1e-9), 2),
        "tail_ratio_tiles": t["tail_ratio"],
        "tail_ratio_pairs": p["tail_ratio"],
        "tail_reduction": round(t["tail_ratio"] / max(p["tail_ratio"], 1e-9), 2),
    }


def run(quick: bool = True, out: str = "BENCH_slam.json"):
    ds = make_dataset("desk0", num_frames=4 if quick else 8, height=64,
                      width=64, num_gaussians=1200, frag_capacity=96)
    cfg = SLAMConfig(
        iters_track=4, iters_map=6, capacity=2048, frag_capacity=96,
        backend="schedule", keyframe=KeyframePolicy(kind="monogs", interval=4),
        fused=True,
    )

    telemetry = _imbalance_telemetry(ds, cfg)

    # Warm-up run compiles the scheduled bundles; the timed run measures the
    # steady state (same convention as bench_slam_fps).
    run_sequence(ds, cfg)
    t0 = time.time()
    res = run_sequence(ds, cfg)
    wall = time.time() - t0
    frames = res.work.frames
    telemetry["scene"] = f"{ds.name}-synthetic"
    telemetry["sched_run"] = {
        "frames": frames,
        "wall_s": round(wall, 3),
        "fps": round(frames / max(wall, 1e-9), 3),
        "dispatches_per_frame": round(res.dispatches / frames, 2),
        "syncs_per_frame": round(res.syncs / frames, 2),
        "ate_cm": round(res.ate * 100, 3),
        "psnr_db": round(res.mean_psnr, 3),
    }

    # Amend (don't clobber) the slam_fps report.
    report = {}
    if os.path.exists(out):
        with open(out) as fh:
            report = json.load(fh)
    report["wsu"] = stamp(telemetry, quick=quick, scene="desk0")
    with open(out, "w") as fh:
        json.dump(report, fh, indent=2)

    emit("wsu/imbalance", 0.0,
         f"max_load_reduction={telemetry['max_load_reduction']}x;"
         f"mean_load_reduction={telemetry['mean_load_reduction']}x;"
         f"tail_tiles={telemetry['tail_ratio_tiles']};"
         f"tail_pairs={telemetry['tail_ratio_pairs']};"
         f"tail_reduction={telemetry['tail_reduction']}x")
    sr = telemetry["sched_run"]
    emit("wsu/sched_run", 1e6 / max(sr["fps"], 1e-9),
         f"fps={sr['fps']};disp_per_frame={sr['dispatches_per_frame']};"
         f"syncs_per_frame={sr['syncs_per_frame']};psnr_db={sr['psnr_db']}")
    return telemetry


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_slam.json")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--full", action="store_true")
    mode.add_argument("--quick", action="store_true",
                      help="quick mode (the default; spelled out for CI smoke jobs)")
    args = ap.parse_args()
    run(quick=not args.full, out=args.out)
