"""Persistent XLA compilation cache for the command-line entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: where it is set, this
module changes nothing. Where it is not, :func:`use_compile_cache`
points the cache at one fixed directory inside the checkout,
``<repo>/.jax_cache`` (git-ignored), so a later process on the same
checkout reuses what an earlier one compiled. The path is part of the
cache key, so it never depends on a temporary name, a pid or the time.

Only ``main()``s call it, never an import: the test suite keeps the
cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


__all__ = ["CACHE_DIR", "use_compile_cache"]
