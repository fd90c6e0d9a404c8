"""JAX's persistent compilation cache, in one place.

A cold run on the chip compiles every stage program from scratch; the
persistent cache lets a later process with the same programs skip that.
The cache key includes its directory, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself, and
nothing else is set then), or else ``<repo>/.jax_cache``, which
``.gitignore`` lists. Entry points call :func:`enable_compile_cache`;
importing a library module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
