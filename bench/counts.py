"""Operations and bytes the rasterizer's work needs, from the workload.

The work of one rendered view is its listed fragments, each blended at the
256 pixel centres of its 16x16 tile.  The counts below come from the blend
equations (``bench/reference.py``: ``blend``), not from how a kernel is
written: stash bytes, padding of the per-tile list to ``K`` and chunk trips
are how a kernel implements the work, so they are never counted.

Forward, per fragment and pixel (32 operations):
  offset from the mean, 2; the conic quadratic form, 9; clamp and scale of
  the exponent, 2; exp, 1; times opacity and the 0.99 clamp, 2; the 1/255
  cut, 2; one minus alpha, 1; the transmittance product, 1; the
  termination test, 1; the blend weight, 2; colour and depth
  accumulation, 8; the final transmittance, 1.
Backward, per fragment and pixel (60 operations): the forward's alpha again
  (17), the weight's and the transmittance suffix's adjoints (14), the
  adjoint of alpha through the clamps (6), of the quadratic form and the
  conic (6), of the mean (8), of the opacity (2), colour (6) and depth (1).

Bytes, per listed fragment: its index (4) and 10 float attributes (mean 2,
conic 3, colour 3, opacity, depth: 40) read once per view; the backward
also writes the 10 attribute adjoints (40).  Per pixel: the forward writes
colour 3, depth and final transmittance (20); the backward reads those and
their 5 adjoints (40).

``fragments`` is the program's ``DeviceWork.fragments``: tile-Gaussian
intersections of every optimisation render, counted before the per-tile
cap, so a tile that overflows ``K`` counts a little more work than the
kernel was given.  Renders outside the optimisation iterations (the
densify and PSNR renders of a keyframe) are not in it, so their kernel
time is counted against no work and the share reads low.
"""

from __future__ import annotations

PIXELS_PER_TILE = 256
FWD_OPS = 32
BWD_OPS = 60
FRAG_BYTES_FWD = 44
FRAG_BYTES_BWD = 84
PIXEL_BYTES_FWD = 20
PIXEL_BYTES_BWD = 40


def forward(fragments: int, pixels: int) -> tuple[float, float]:
    """(operations, bytes) of forward renders with ``fragments`` listed
    fragments over ``pixels`` pixels."""
    return (float(FWD_OPS) * fragments * PIXELS_PER_TILE,
            float(FRAG_BYTES_FWD) * fragments + float(PIXEL_BYTES_FWD) * pixels)


def backward(fragments: int, pixels: int) -> tuple[float, float]:
    return (float(BWD_OPS) * fragments * PIXELS_PER_TILE,
            float(FRAG_BYTES_BWD) * fragments + float(PIXEL_BYTES_BWD) * pixels)


def roofline_pct(ops: float, nbytes: float, seconds: float,
                 peaks: dict) -> float | None:
    """The least time the chip needs for the work, over the time taken, in
    percent: max(ops / peak FLOP/s, bytes / peak bytes/s) / seconds."""
    if seconds <= 0 or ops <= 0:
        return None
    least = max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
