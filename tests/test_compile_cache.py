"""The entry points' persistent compile cache (``repro.launch.cache``).

Each case runs in a fresh interpreter: the cache is process-wide JAX
configuration, and the test process itself never turns it on.
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.launch.cache import REPO_CACHE_DIR

_REPO = pathlib.Path(__file__).resolve().parents[1]

_PROBE = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch.cache import use_compile_cache
    where = use_compile_cache()
    assert where == jax.config.jax_compilation_cache_dir, (
        where, jax.config.jax_compilation_cache_dir)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    print(where)
""")


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(_REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jc")
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    where = out.stdout.strip().splitlines()[-1]
    if env_dir:
        # The variable stands, and the compile landed there.
        assert where == str(tmp_path / "jc")
        assert any((tmp_path / "jc").iterdir())
    else:
        # A fixed path in the checkout, listed in .gitignore.
        assert where == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR.parent == _REPO
        ignored = (_REPO / ".gitignore").read_text().split()
        assert f"{REPO_CACHE_DIR.name}/" in ignored
