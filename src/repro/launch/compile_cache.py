"""Persistent JAX compilation cache for the entry points.

Each entry point calls ``enable_compile_cache()`` from its ``main``, never
on import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets nothing.  Otherwise the cache lives at the fixed
path ``<checkout>/.cache/jax`` — the directory is part of the cache key,
so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".cache", "jax")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
