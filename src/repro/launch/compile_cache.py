"""Where JAX keeps its persistent compilation cache.

A chip run may start with no compiled program, and compiling the served
path is a large part of a cold start, so the launchers keep compiled
programs on disk for the next process to find.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so that a later run in the same checkout finds what this one wrote
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
