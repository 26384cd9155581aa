"""Persistent XLA compilation cache for the entry points.

A cold process spends a large share of a short run compiling; the
persistent cache lets the next process that compiles the same programs
skip that.  Call :func:`enable_compile_cache` before the first compile.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, so every process of this checkout
# finds what an earlier one wrote (git-ignored)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken its
    directory from it, and that setting stays.  Otherwise the cache goes
    to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
