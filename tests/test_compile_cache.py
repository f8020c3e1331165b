"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX state, and a test worker goes on to run other files.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.compile_cache import REPO_CACHE_DIR

REPO = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
print(path)
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
"""


def _run(env_dir, compile_one):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          PROBE.format(compile=compile_one)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split("\n")[:2]


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_dir_comes_from_env_else_repo(tmp_path, from_env):
    if from_env:
        # the variable's directory is used as it is, and written to
        cache = tmp_path / "cache"
        assert _run(cache, compile_one=True) == [str(cache)] * 2
        assert any(cache.iterdir())
    else:
        # a fixed path inside the checkout: no pid, time or temporary name
        assert _run(None, compile_one=False) == [str(REPO_CACHE_DIR)] * 2
        assert REPO_CACHE_DIR == REPO / ".jax_cache"
