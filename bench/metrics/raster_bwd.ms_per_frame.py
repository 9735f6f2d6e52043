"""Device time of the backward rasterizer kernels and the GMU block
cumulative sum (``kernels/tile_render_bp.py``, ``kernels/gmu.py``) in the
window, per frame."""

from bench import tracing

KERNELS = ("tile_render_bwd", "block_cumsum")


def read(run):
    ns = tracing.kernel_ns(run.trace, KERNELS)
    return ns * 1e-6 / run.frames if ns > 0 else None
