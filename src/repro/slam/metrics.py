"""SLAM evaluation metrics: ATE (with SE(3) alignment), PSNR, and the work
counters that the paper's FPS gains are made of (fragments blended, alive
Gaussians, pixels rendered).

Two counter forms:

* :class:`WorkCounters` — host-side running totals over a whole run (Python
  ints, no overflow), the public accounting surface of ``SLAMResult``.
* :class:`DeviceWork` — a small int32 pytree threaded through the engine's
  ``lax.scan`` carries so per-iteration accounting happens **on device**;
  the engine fetches it once per frame (not per iteration) and absorbs it
  into the host ``WorkCounters``, which bounds the int32 range per frame.
  The session layer accumulates a *run-cumulative* :class:`WideWork` on
  device (fetched once at finalize): a hi/lo carry-split pair of int32
  ``DeviceWork`` words (``total = hi * 2**30 + lo``) that widens the
  run-cumulative range to ~2^61 per counter while staying inside int32
  arithmetic — a paper-resolution stream (~15M fragments per keyframe)
  fits for ~10^13 keyframes, so long high-resolution runs no longer need
  per-frame fetches to avoid wrap (``StepResult.work`` remains the
  per-frame int32 snapshot; the per-frame bound is unchanged).
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple

import jax.numpy as jnp
import numpy as np


class DeviceWork(NamedTuple):
    """Per-frame on-device work accumulator (int32 scalars).

    The last three fields are the sparse stable/unstable counters, and all
    three count **mapping** work only — tracking optimizes the pose, not
    Gaussian params, so it contributes zero to each.  In dense mode
    ``unstable_gaussians`` equals the mapping share of ``gaussians_iters``
    (every alive Gaussian is optimized each mapping iteration),
    ``sched_programs`` counts the subtile programs (chunk trips) mapping
    rasterization streams, and ``skipped_fragments`` is 0 (nothing is
    dropped)."""

    fragments: jnp.ndarray       # tile-Gaussian intersections processed
    pixels: jnp.ndarray          # pixels rendered
    gaussians_iters: jnp.ndarray  # alive Gaussians x iterations
    iterations: jnp.ndarray
    unstable_gaussians: jnp.ndarray  # optimized Gaussians x mapping iters
    sched_programs: jnp.ndarray      # mapping subtile programs (chunk trips)
    skipped_fragments: jnp.ndarray   # fragments dropped by the stable mask
    densify_dropped: jnp.ndarray     # new Gaussians dropped: storage full
    frag_build_rows: jnp.ndarray     # rows swept by fragment-list builds
    #                                  (paged mode sweeps the visible view,
    #                                   not the whole map)


def device_work_zero() -> DeviceWork:
    # One buffer per field: the fused bundles donate ``work``, and a buffer
    # donated twice in one call is refused.
    return DeviceWork(*(jnp.zeros((), jnp.int32) for _ in DeviceWork._fields))


def device_work_add(w: DeviceWork, fragments, pixels, alive,
                    unstable=None, programs=0, skipped=0) -> DeviceWork:
    """jit/scan-safe equivalent of ``WorkCounters.add``; all args () int32.
    ``unstable`` defaults to ``alive`` (dense mode: every alive Gaussian is
    optimized)."""
    one = jnp.asarray(1, jnp.int32)
    if unstable is None:
        unstable = alive
    return DeviceWork(
        fragments=w.fragments + jnp.asarray(fragments, jnp.int32),
        pixels=w.pixels + jnp.asarray(pixels, jnp.int32),
        gaussians_iters=w.gaussians_iters + jnp.asarray(alive, jnp.int32),
        iterations=w.iterations + one,
        unstable_gaussians=w.unstable_gaussians + jnp.asarray(unstable, jnp.int32),
        sched_programs=w.sched_programs + jnp.asarray(programs, jnp.int32),
        skipped_fragments=w.skipped_fragments + jnp.asarray(skipped, jnp.int32),
        densify_dropped=w.densify_dropped,
        frag_build_rows=w.frag_build_rows,
    )


def device_work_merge(a: DeviceWork, b: DeviceWork) -> DeviceWork:
    """Elementwise sum of two *per-frame* accumulators (jit/scan-safe).
    Run-cumulative totals must use :class:`WideWork` instead — a plain
    int32 sum wraps after ~2e9 fragments."""
    return DeviceWork(*(jnp.asarray(x, jnp.int32) + jnp.asarray(y, jnp.int32)
                        for x, y in zip(a, b)))


# ---------------------------------------------------------------------------
# run-cumulative work, widened past int32 (hi/lo carry split)
# ---------------------------------------------------------------------------

_WIDE_SHIFT = 30
_WIDE_BASE = 1 << _WIDE_SHIFT          # lo word lives in [0, 2**30)


class WideWork(NamedTuple):
    """Run-cumulative work counters widened past int32 without needing
    x64: two int32 ``DeviceWork`` words per counter, ``total = hi *
    2**30 + lo`` with ``lo`` kept in ``[0, 2**30)`` by a per-add carry.
    Range ~2^61 per counter — the session layer's device-resident
    accumulator (fetched once at finalize), immune to the wrap a flat
    int32 run-cumulative ``DeviceWork`` hits after ~2e9 fragments."""

    hi: DeviceWork    # units of 2**30
    lo: DeviceWork    # remainder in [0, 2**30)


def wide_work_zero() -> WideWork:
    return WideWork(hi=device_work_zero(), lo=device_work_zero())


def wide_work_add(acc: WideWork, w: DeviceWork) -> WideWork:
    """``acc + w`` (jit/scan-safe).  ``w`` is a non-negative per-frame
    int32 snapshot; it is carry-split before the add, so no intermediate
    exceeds ``2**31`` for ANY representable ``w`` — large per-frame counts
    cannot wrap the accumulator."""
    his, los = [], []
    for h, l, x in zip(acc.hi, acc.lo, w):
        x = jnp.asarray(x, jnp.int32)
        lo2 = l + (x & (_WIDE_BASE - 1))        # both < 2**30: no wrap
        his.append(h + (x >> _WIDE_SHIFT) + (lo2 >> _WIDE_SHIFT))
        los.append(lo2 & (_WIDE_BASE - 1))
    return WideWork(hi=DeviceWork(*his), lo=DeviceWork(*los))


def wide_work_totals(acc: WideWork) -> dict:
    """Host-side exact totals (Python ints) of a fetched :class:`WideWork`:
    ``{field: hi * 2**30 + lo}``."""
    return {f: int(h) * _WIDE_BASE + int(l)
            for f, h, l in zip(DeviceWork._fields, acc.hi, acc.lo)}


class ImbalanceStats(NamedTuple):
    """WSU workload-imbalance counters over one grid's program loads.

    ``tail_ratio`` (max/mean fragments per program) is the quantity pairwise
    scheduling attacks: it is how many times longer the heaviest program runs
    than the average one, i.e. the idle fraction of a parallel machine."""

    max_load: float    # fragments in the heaviest program
    mean_load: float   # mean fragments per program
    tail_ratio: float  # max / mean (1.0 = perfectly balanced)


def imbalance_stats(loads) -> ImbalanceStats:
    """Per-program fragment-load imbalance.  ``loads`` is (P,) — per-tile
    counts for the unscheduled grid, ``schedule.pair_loads`` for the WSU
    grid."""
    loads = np.asarray(loads, np.float64)
    mx = float(loads.max()) if loads.size else 0.0
    mean = float(loads.mean()) if loads.size else 0.0
    return ImbalanceStats(max_load=mx, mean_load=mean,
                          tail_ratio=mx / max(mean, 1e-9))


def align_umeyama(src: np.ndarray, dst: np.ndarray):
    """Closed-form SE(3) alignment (no scale) of src -> dst, both (F, 3)."""
    mu_s, mu_d = src.mean(0), dst.mean(0)
    cs, cd = src - mu_s, dst - mu_d
    H = cs.T @ cd
    U, _, Vt = np.linalg.svd(H)
    S = np.diag([1.0, 1.0, np.sign(np.linalg.det(Vt.T @ U.T))])
    R = Vt.T @ S @ U.T
    t = mu_d - R @ mu_s
    return R, t


def ate_rmse(est_w2c: List[np.ndarray], gt_w2c: List[np.ndarray]) -> float:
    """Absolute Trajectory Error (RMSE, meters) after SE(3) alignment —
    the paper's tracking-accuracy metric (reported in cm in tables)."""
    est_c = np.stack([np.linalg.inv(p)[:3, 3] for p in est_w2c])
    gt_c = np.stack([np.linalg.inv(p)[:3, 3] for p in gt_w2c])
    R, t = align_umeyama(est_c, gt_c)
    aligned = est_c @ R.T + t
    return float(np.sqrt(np.mean(np.sum((aligned - gt_c) ** 2, axis=-1))))


def psnr_np(a: np.ndarray, b: np.ndarray, max_val: float = 1.0) -> float:
    mse = float(np.mean((a - b) ** 2))
    return 10.0 * np.log10(max_val**2 / max(mse, 1e-12))


@dataclasses.dataclass
class WorkCounters:
    """Algorithmic work — the quantities RTGS's speedups reduce."""

    fragments: int = 0        # tile-Gaussian intersections processed
    pixels: int = 0           # pixels rendered (downsampling reduces this)
    gaussians_iters: int = 0  # alive Gaussians x iterations (pruning reduces)
    iterations: int = 0
    frames: int = 0
    unstable_gaussians: int = 0  # optimized Gaussians x mapping iters
    #                              (sparse_opt reduces)
    sched_programs: int = 0      # mapping subtile programs (chunk trips)
    skipped_fragments: int = 0   # fragments dropped by the stable mask
    densify_dropped: int = 0     # new Gaussians dropped: storage full
    frag_build_rows: int = 0     # rows swept by fragment-list builds

    def add(self, fragments: int, pixels: int, alive: int):
        self.fragments += int(fragments)
        self.pixels += int(pixels)
        self.gaussians_iters += int(alive)
        self.iterations += 1

    def absorb(self, dev) -> None:
        """Fold a fetched per-frame :class:`DeviceWork` snapshot (already on
        host, e.g. via ``jax.device_get``) into the running totals."""
        self.fragments += int(dev.fragments)
        self.pixels += int(dev.pixels)
        self.gaussians_iters += int(dev.gaussians_iters)
        self.iterations += int(dev.iterations)
        self.unstable_gaussians += int(dev.unstable_gaussians)
        self.sched_programs += int(dev.sched_programs)
        self.skipped_fragments += int(dev.skipped_fragments)
        self.densify_dropped += int(dev.densify_dropped)
        self.frag_build_rows += int(dev.frag_build_rows)

    def merged_with(self, other: "WorkCounters") -> "WorkCounters":
        return WorkCounters(
            fragments=self.fragments + other.fragments,
            pixels=self.pixels + other.pixels,
            gaussians_iters=self.gaussians_iters + other.gaussians_iters,
            iterations=self.iterations + other.iterations,
            frames=self.frames + other.frames,
            unstable_gaussians=self.unstable_gaussians + other.unstable_gaussians,
            sched_programs=self.sched_programs + other.sched_programs,
            skipped_fragments=self.skipped_fragments + other.skipped_fragments,
            densify_dropped=self.densify_dropped + other.densify_dropped,
            frag_build_rows=self.frag_build_rows + other.frag_build_rows,
        )
