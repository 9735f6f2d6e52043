"""The rasterizer kernels compile for a TPU v5e at TUM RGB-D size.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles each Pallas kernel for a *described* v5e and refuses what the
chip would refuse (block shapes off the (8, 128) tiling, unaligned lane
slices, scalar loads from vector memory).  Sizes are the deployment's:
640x480 frames (1,200 tiles), K=128 fragments per tile, chunk 16, and the
``map_window=4`` batched mapping render (4 x 1,200 stacked tile rows).

The topology is described inside a module fixture, never at import, so
only the test process that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.sorting import make_tile_grid
from repro.kernels.ref import NUM_ATTRS, PIX
from repro.kernels.tile_render import tile_render_fwd, tile_render_fwd_sched
from repro.kernels.tile_render_bp import (
    NUM_GRADS,
    tile_render_bwd,
    tile_render_bwd_sched,
)

GRID = make_tile_grid(480, 640)
K = 128
CHUNK = 16
T = GRID.num_tiles


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the persistent
    # cache without one; keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(sharding, views):
    rows = views * T

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return {
        "attrs": sds((rows, NUM_ATTRS, K)),
        "count": sds((rows,), jnp.int32),
        "slots": sds((rows,), jnp.int32),   # 1,200 tiles pair up evenly
        "stash": sds((rows, K, PIX)),
        "g_color": sds((rows, 3, PIX)),
        "g_pix": sds((rows, PIX)),
    }


def _lower(name, s):
    static = dict(grid=GRID, chunk=CHUNK, interpret=False, tiles_per_view=T)
    if name == "fwd":
        return tile_render_fwd.lower(s["attrs"], s["count"], **static)
    if name == "fwd_sched":
        return tile_render_fwd_sched.lower(s["attrs"], s["slots"], s["slots"],
                                           **static)
    if name == "bwd":
        return tile_render_bwd.lower(s["attrs"], s["count"], s["stash"],
                                     s["g_color"], s["g_pix"], s["g_pix"],
                                     **static)
    return tile_render_bwd_sched.lower(s["attrs"], s["slots"], s["slots"],
                                       s["stash"], s["g_color"], s["g_pix"],
                                       s["g_pix"], **static)


@pytest.mark.parametrize("name,views", [
    ("fwd", 1),
    ("fwd_sched", 1),
    ("bwd", 1),
    ("bwd_sched", 1),
    ("fwd_sched", 4),
    ("bwd_sched", 4),
])
def test_kernel_compiles_for_v5e(one_chip, name, views):
    compiled = _lower(name, _shapes(one_chip, views)).compile()
    # A Mosaic kernel, not the interpreter's HLO loop, is in the program.
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    rows = views * T
    if name.startswith("fwd"):
        want = rows * (K * PIX + 3 * PIX + 2 * PIX) * 4   # stash + pixel rows
    else:
        want = rows * NUM_GRADS * K * 4
    assert mem.output_size_in_bytes >= want
