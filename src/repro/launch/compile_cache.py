"""JAX persistent compilation cache for the entry points.

Call :func:`enable_compile_cache` once, before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else
is set.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed path,
because the directory is part of what a later process must find again
(a temporary name, a pid or the time would never hit).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
