"""Persistent JAX compilation cache for the serving and benchmark entry
points.

Call `enable_compile_cache()` once per process, before the first compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
nothing is set here.  Otherwise the cache lives at a fixed directory
inside the checkout (``<repo>/.jax_cache``, git-ignored): the directory
is part of what a later run looks up, so it is never derived from a
temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
