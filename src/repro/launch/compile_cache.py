"""Persistent XLA compile cache for the repository's entry points.

Every script a user runs (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``, ``scripts/crash_resume_check.py``) calls
``enable_compile_cache()`` before its first compile, so a second run of
the same program loads its executables instead of compiling them again.
Tests do not call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, because the directory is part of the cache key and a path derived
from a tempdir, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
