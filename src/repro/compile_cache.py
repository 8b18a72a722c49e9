"""JAX's persistent compilation cache, kept at one fixed place.

A cold process recompiles every bucket x encoding executable (tens of
seconds for each Mosaic-compiled kernel).  :func:`enable` points JAX's
persistent cache at a stable directory so the next process finds them:

  * where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here overrides it;
  * otherwise the cache goes to ``.jax_cache/`` at the checkout root.

The path is part of each entry's key, so the directory must not move.
Entry points call :func:`enable` once at start-up; nothing calls it at
import time.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
