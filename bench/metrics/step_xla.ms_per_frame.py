"""Device-busy time not covered by the Pallas kernels, per frame: the
projection, fragment build, WSU schedule, losses, Adam, pruning and
densification that XLA compiles around the kernels."""

from bench import tracing

KERNELS = ("tile_render_fwd", "tile_render_bwd", "block_cumsum")


def read(run):
    busy = tracing.busy_ns(run.trace)
    if busy <= 0:
        return None
    return max(busy - tracing.kernel_ns(run.trace, KERNELS), 0.0) * 1e-6 / run.frames
