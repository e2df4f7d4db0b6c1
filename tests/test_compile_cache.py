"""Where JAX's persistent compilation cache lives (repro.compile_cache)."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_directory_is_used_and_written(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the helper sets no directory of its own."""
    cache = tmp_path / "cache"
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
        import jax, jax.numpy as jnp
        from repro.compile_cache import configure_compile_cache
        got = configure_compile_cache()
        assert got == {str(cache)!r}, got
        assert jax.config.jax_compilation_cache_dir == got
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert any(cache.iterdir())


def test_default_is_the_checkouts_ignored_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.configure_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("module", ["repro", "repro.serving.session",
                                    "repro.core.flexbuild"])
def test_importing_the_library_leaves_the_cache_off(module):
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
        import importlib, jax
        importlib.import_module({module!r})
        assert jax.config.jax_compilation_cache_dir is None
    """)
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
