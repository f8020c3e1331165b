"""JAX's persistent compilation cache, as the repo's entry points use it.

Nothing here runs at import: an entry point (``chip_smoke.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` once, before its
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory.  Otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache``: a later run finds its entries only under the same
    path, so it never carries a pid, a time or a temporary name.  Every
    compile is kept, since the store's kernels compile in well under JAX's
    default one-second threshold.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
