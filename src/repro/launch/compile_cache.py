"""JAX persistent compilation cache for the entry points.

The entry points (``repro.launch.train``, ``repro.launch.serve``,
``chip_smoke.py``) call :func:`enable_compile_cache` before their first
compile; importing the library never touches the cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here.  Otherwise the cache lives at a fixed ``<repo>/.jax_cache``
(listed in ``.gitignore``): the directory is part of the cache key, so a
path that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
