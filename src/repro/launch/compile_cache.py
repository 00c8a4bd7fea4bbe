"""Persistent JAX compilation cache for the repo's entry points.

``chip_smoke.py``, the benchmarks, the examples and ``launch/train.py``
call :func:`enable` from their ``__main__`` path. Importing the library
never does, so tests keep JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache goes to one fixed directory in
the checkout, ``<repo>/.jax_cache`` (git-ignored): a cache whose path
moved between runs would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
