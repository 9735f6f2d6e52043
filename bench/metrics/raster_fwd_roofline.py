"""Forward rasterizer kernels' share of their roofline: the least time the
chip needs for the window's forward blend work (``bench/counts.py``) over
the kernels' device time."""

from bench import counts, tracing

KERNELS = ("tile_render_fwd",)


def read(run):
    ns = tracing.kernel_ns(run.trace, KERNELS)
    ops, nbytes = counts.forward(run.work["fragments"], run.work["pixels"])
    return counts.roofline_pct(ops, nbytes, ns * 1e-9, run.peaks)
