"""Persistent XLA compilation cache for the entry points.

`JAX_COMPILATION_CACHE_DIR`, when set, is left to JAX, which reads it on
its own; no other directory is configured. Otherwise the cache lives at
the fixed `<checkout>/.jax_cache`: the path is part of the cache key, so
a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
