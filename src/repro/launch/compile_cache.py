"""JAX's persistent compilation cache, placed for the program's entry points.

Only entry points (``main`` functions and top-level scripts) call
``enable_compile_cache``; no library module turns the cache on at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs in ``$JAX_COMPILATION_CACHE_DIR`` when it is
    set, else in the fixed ``<repo>/.jax_cache`` (the path is part of the
    cache key, so it must not move). Returns the directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
