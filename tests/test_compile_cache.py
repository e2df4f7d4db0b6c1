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
    names = ["jax_compilation_cache_dir", *compile_cache.METADATA_KEY_CONFIG]
    before = {name: getattr(jax.config, name) for name in names}
    try:
        got = compile_cache.configure_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
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


_SCOPED = """
import jax


def f(x):
    with jax.named_scope({scope!r}):
        return x * 2 + 1
"""


def _compile_scoped(cache, where, scope):
    """Compile ``f`` under ``scope`` from a module in ``where`` against
    the cache: (programs loaded from the cache, op_name paths)."""
    where.mkdir(exist_ok=True)
    (where / "scoped.py").write_text(_SCOPED.format(scope=scope))
    code = textwrap.dedent(f"""
        import re, sys
        sys.path[:0] = [{str(where)!r}, {os.path.join(ROOT, 'src')!r}]
        import jax, jax.numpy as jnp
        from repro.compile_cache import configure_compile_cache
        configure_compile_cache()
        hits = []
        jax.monitoring.register_event_listener(
            lambda e, **kw: hits.append(e)
            if e == "/jax/compilation_cache/cache_hits" else None)
        import scoped
        x = jnp.ones(8)
        hits.clear()
        text = jax.jit(scoped.f).lower(x).compile().as_text()
        print(len(hits), sorted(set(re.findall(r'op_name="([^"]*)"', text))))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    hits, names = out.stdout.strip().split(" ", 1)
    return int(hits), names


def test_cached_program_keeps_its_own_scope_names(tmp_path):
    """A program that differs only in its named scopes compiles anew, so
    a profile reads the names of the code that ran; the same code from
    another directory loads the cached program."""
    cache = tmp_path / "cache"
    hits, names = _compile_scoped(cache, tmp_path / "a", "sample.hop0")
    assert hits == 0 and "sample.hop0/" in names
    hits, names = _compile_scoped(cache, tmp_path / "a", "gather.features")
    assert hits == 0 and "gather.features/" in names
    assert "sample.hop0" not in names
    hits, names = _compile_scoped(cache, tmp_path / "b", "sample.hop0")
    assert hits == 1 and "sample.hop0/" in names
