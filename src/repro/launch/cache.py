"""Persistent XLA compile cache for the entry points.

Entry scripts (``chip_smoke.py``, ``examples/*``, ``benchmarks/*``) call
:func:`use_compile_cache` once before their first compile; the library
never calls it on import, and the tests never call it.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache lives at one fixed directory of the
checkout, ``<repo>/.jax_cache`` (gitignored): a fixed path, so a second
process finds what the first one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
