"""Persistent compilation cache for the entry points.

Called first thing by ``launch.serve.main``, ``launch.train.main`` and
``chip_smoke.py`` — never at library import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed here.  Otherwise the cache goes to ``<checkout>/.jax_cache``, a
directory fixed by the package's own location (the cache key includes
nothing that moves, so a second process finds what the first compiled).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "default_cache_dir"]


def default_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` for the src layout (src/repro/launch/…)."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Make sure JAX's persistent cache is on; returns its directory.

    Must run before the process compiles anything: JAX decides once, at
    the first compile, whether the persistent cache is in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(default_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
