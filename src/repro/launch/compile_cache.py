"""Persistent JAX compilation cache for the entry points.

Called by ``launch/serve.main``, ``benchmarks/run.main`` and
``chip_smoke.py`` before their first compile, never at import, so library
users and the tests keep JAX's own defaults.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# the cache key (a per-run temp directory would never hit)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it is left alone; otherwise the cache goes to ``REPO_CACHE_DIR``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
