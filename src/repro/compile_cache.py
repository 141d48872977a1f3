"""Persistent JAX compilation cache at a placeable, stable path.

A cold TPU run compiles every executable; JAX's persistent cache lets a
later process load them instead.  The cache key includes the directory, so
the directory must not move between runs: it is never derived from a
temporary name, a process id or the time.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing.
- otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: fixed default: <checkout>/.jax_cache (this file is src/repro/compile_cache.py)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the process compiles anything: JAX fixes the cache at the
    first compile."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
