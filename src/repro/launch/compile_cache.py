"""JAX's persistent compilation cache at one fixed path per checkout.

The cache directory is part of what a cached executable is found by, so
a run only reuses an earlier run's compiles when both point at the same
directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting
and wins untouched; otherwise every entry point of this checkout uses
``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
