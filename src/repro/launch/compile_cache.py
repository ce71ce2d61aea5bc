"""Where JAX keeps its persistent compilation cache.

A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX, and nothing else is
set.  Otherwise the cache sits at one fixed directory inside the checkout,
``.jax_cache/`` (git-ignored): the directory is part of what a later run
must find, so it never depends on a temporary name, a process id or the
time.  The entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``) call :func:`enable_compile_cache` before they
compile anything.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
