"""JAX's persistent compilation cache at a fixed place.

Every process that drives the device starts with no compiled code, so
entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`configure_compile_cache` first. Importing the library never turns
the cache on: tests and embedding applications keep JAX's defaults.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# the checkout root: src/repro/compile_cache.py → two levels up from src/
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache lives in the checkout's
    git-ignored ``.jax_cache``: a fixed path, because the path is part of
    what a cached program is found by."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
