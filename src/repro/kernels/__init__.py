"""Pallas kernels of the rasterizer (forward, backward) and the GMU merge."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode: an explicit flag wins; ``None`` derives it from
    the platform — the interpreter only where the kernels would run on the
    CPU (which has no Mosaic backend), compiled kernels everywhere else."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
