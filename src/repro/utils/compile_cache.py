"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` before their first
compile. A full-width model compiles for minutes, and separate processes
on one machine share compiled programs through this cache.
"""
from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, names the directory: JAX
    reads it itself and this sets nothing. Otherwise the cache is the
    fixed ``<repo>/.jax_cache``; the directory is part of a cached
    entry's identity, so it never moves between runs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
