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


# A cached program keeps the op metadata it was compiled with, and JAX's
# default key leaves that metadata out: a step loaded from a cache that an
# older build filled would then profile under the older build's names, or
# none (the ``jax.named_scope`` scopes of DESIGN.md §10). So the key holds
# the metadata, with source files by base name, so that a moved checkout
# still hits. The metadata holds each op's source stack, its callers'
# lines included: an edit that moves those lines compiles anew, once.
METADATA_KEY_CONFIG = {
    "jax_compilation_cache_include_metadata_in_key": True,
    "jax_hlo_source_file_canonicalization_regex": r".*/",
}


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache, keyed by the programs'
    metadata too (``METADATA_KEY_CONFIG``), and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here. Otherwise the cache lives in the checkout's
    git-ignored ``.jax_cache``: a fixed path, because the path is part of
    what a cached program is found by."""
    for name, value in METADATA_KEY_CONFIG.items():
        jax.config.update(name, value)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
