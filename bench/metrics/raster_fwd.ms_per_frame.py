"""Device time of the forward rasterizer kernels (the Pallas blend of
``kernels/tile_render.py``) in the window, per frame."""

from bench import tracing

KERNELS = ("tile_render_fwd",)


def read(run):
    ns = tracing.kernel_ns(run.trace, KERNELS)
    return ns * 1e-6 / run.frames if ns > 0 else None
