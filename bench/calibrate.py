#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at the cell's size.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--control-seeds 3]

For each seed, in one process (so the program compiles once): the cell's
streams from that seed, the system's first ``checked_frames`` frames through
the window's own calls, the reference, and the comparison.  For the first
``--control-seeds`` seeds the lower-precision control (the reference at
``high``) is put in the program's place and compared too.  One JSON line per
reading; ``PERF.md`` records what the limits were set from.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, harness, spec, traffic  # noqa: E402
from bench import run as bench_run  # noqa: E402


def as_program(ref: list) -> list:
    """The reference's records in the program's per-step layout."""
    steps = []
    for i in range(len(ref[0])):
        steps.append([dict(r[i], is_kf="psnr" in r[i]) for r in ref])
    return steps


def frame_gaps(program: list, ref: list) -> list:
    """Per stream, each frame's pose gap and relative loss gaps (the
    numbers of ``compare.numbers`` before their largest is taken)."""
    out = []
    for i, ref_steps in enumerate(ref):
        rows = []
        for step, r in zip(program, ref_steps):
            p = step[i]
            row = {"pose": float(np.max(np.abs(p["pose"] - r["pose"]))),
                   "track_loss": compare._rel(p["track_losses"],
                                              r["track_losses"]),
                   "track_loss_first": compare._rel(p["track_losses"][:1],
                                                    r["track_losses"][:1])}
            if p["is_kf"] and "map_losses" in r:
                row["map_loss"] = compare._rel(p["map_losses"], r["map_losses"])
                row["map_loss_first"] = compare._rel(p["map_losses"][:1],
                                                     r["map_losses"][:1])
                row["psnr_db"] = abs(p["psnr"] - r["psnr"])
            rows.append(row)
        out.append(rows)
    return out


def checked_steps(cfg, mix, streams) -> list:
    system = harness.system(streams, cfg, mix["session_seed"],
                                  mix["max_frames"])
    warm = harness.warm_up(system, mix["checked_frames"])
    system.close()
    del system
    gc.collect()
    return warm


def readings(cell, cfg, mix, seed: int, control: bool,
             witness: str | None = None) -> dict:
    """Program, and optionally control and a second witness (the program
    on another rasterizer backend), against the reference on one seed."""
    frames = mix["checked_frames"]
    streams = [traffic.make_stream(s, seed, cfg, mix) for s in mix["streams"]]
    warm = checked_steps(cfg, mix, streams)
    ref = harness.run_reference(streams, cfg, frames, mix["session_seed"])
    out = {"seed": seed, "program": compare.numbers(warm, ref),
           "program_frames": frame_gaps(warm, ref)}
    if witness:
        other = copy.deepcopy(cfg)
        other["slam"]["backend"] = witness
        out["witness_" + witness] = compare.numbers(
            checked_steps(other, mix, streams), ref)
    if control:
        ctl = harness.run_reference(streams, cfg, frames, mix["session_seed"],
                                    precision="high")
        out["control"] = compare.numbers(as_program(ctl), ref)
        out["control_frames"] = frame_gaps(as_program(ctl), ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--witness", default=None,
                    help="also run the program on this rasterizer backend")
    ap.add_argument("--witness-seeds", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cfg, mix = cell.config, cell.traffic
    if args.rehearse:
        cfg, mix = bench_run.rehearsal_sizes(cfg, mix)
    try:
        bench_run.start_jax(cell.chips, args.rehearse)
    except bench_run.NoChip as e:
        return int(e.code)
    for i, seed in enumerate(args.seeds):
        r = readings(cell, cfg, mix, seed, i < args.control_seeds,
                     args.witness if i < args.witness_seeds else None)
        print(json.dumps(r), flush=True)
        bench_run.log(f"seed {seed} done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
