"""JAX's persistent compilation cache for the repo's entry points.

A process that compiles the same step as an earlier one reads the
executable back instead of compiling again.  Entry points call
:func:`use_compile_cache` once, before their first compile:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
  directory and nothing here sets another;
* otherwise the cache is ``<checkout>/.jax_cache`` (git-ignored).  The
  path is fixed because it is part of the cache key: a directory named
  after a temporary path, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/train/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
