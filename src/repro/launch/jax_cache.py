"""JAX's persistent compilation cache, set up by each entry point.

Entry points (``launch/train.py``, ``launch/serve.py`` and
``chip_smoke.py``) call :func:`enable_compilation_cache` first thing in
``main``; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, so that a later process on the same checkout reads what an
# earlier one compiled: a directory named per run would never be hit
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the
    variable itself and this sets nothing; otherwise the cache lives in
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
