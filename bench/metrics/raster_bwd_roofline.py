"""Backward rasterizer kernels' share of their roofline: the least time the
chip needs for the window's backward blend work (``bench/counts.py``) over
the backward kernels' device time (the GMU cumulative sum is not counted
as work and its time is left out)."""

from bench import counts, tracing

KERNELS = ("tile_render_bwd",)


def read(run):
    ns = tracing.kernel_ns(run.trace, KERNELS)
    ops, nbytes = counts.backward(run.work["fragments"], run.work["pixels"])
    return counts.roofline_pct(ops, nbytes, ns * 1e-9, run.peaks)
