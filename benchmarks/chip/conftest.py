"""The benchmark's own tests run on the CPU at tiny sizes."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture
def rehearse(monkeypatch, tmp_path):
    """run_cell without a chip, its compile cache kept out of the
    checkout and JAX's settings restored afterwards."""
    import jax

    import repro.compile_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(cc, "configure_compile_cache",
                        lambda: str(tmp_path / "no-cache"))

    def run(cell, seed=2 ** 31 + 9, trace=False, **kw):
        from benchmarks.chip import harness

        return harness.run_cell(cell, seed, 2.0, trace,
                                require_chip=False, **kw)

    yield run
    for k, v in saved.items():
        jax.config.update(k, v)
