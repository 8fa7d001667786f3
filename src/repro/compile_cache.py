"""JAX persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, each benchmark's ``__main__``, the
examples) call :func:`setup_compile_cache` once before their first
compile; the library never calls it, so importing ``repro`` changes no
JAX setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this helper
  sets nothing.
* unset: the cache lives in ``.jax_cache/`` at the repository root. The
  path is fixed (never a tempdir, pid or timestamp) because the
  directory is where a later process looks for its entries.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
