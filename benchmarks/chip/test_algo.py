"""CPU rehearsals of the sharded Graphalytics cell through the harness,
on four virtual CPU devices at Graph500 scale 12: the program matching
the references with nothing compiled in the window, the result line's
metric keys, and the control and each fault a sharded analytics cell
can have coming out not correct. The four devices need a process of
their own: JAX fixes its device count when it starts."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness

CELL = "graph500_22.algo_sharded"
GRAPH = {"scale": 12, "n_edges": 48000}


def _exchange_dropped(mp):
    """The last chip's contribution left out of every exchange."""
    from repro.engines.grape.engine import GrapeEngine

    orig = GrapeEngine._scatter
    none = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}

    def scatter(self, fa, owned_vals, combiner, use_weights):
        out = orig(self, fa, owned_vals, combiner, use_weights)
        last = jax.lax.axis_index("data") == self.n_frags - 1
        return jnp.where(last, jnp.asarray(none[combiner], out.dtype), out)

    mp.setattr(GrapeEngine, "_scatter", scatter)


def _depths_altered(mp):
    """BFS depths one too deep on the second fragment, where the
    fixpoint returns them."""
    import repro.engines.grape.algorithms as alg

    orig = alg.run_pregel

    def run_pregel(engine, prog, *args, **kw):
        out = orig(engine, prog, *args, **kw)
        if prog.residual_key != "depth":
            return out
        d = out["depth"]
        second = jnp.arange(d.shape[0]) // engine.frags.v_per_frag == 1
        return {**out, "depth": jnp.where(second, d + 1.0, d)}

    mp.setattr(alg, "run_pregel", run_pregel)


def _state_unchanged(mp):
    """Every superstep returns the state it was given."""
    import repro.engines.grape.algorithms as alg

    orig = alg.run_pregel

    def run_pregel(engine, prog, *args, **kw):
        still = dataclasses.replace(prog, update=lambda st, msgs, step: st)
        return orig(engine, still, *args, **kw)

    mp.setattr(alg, "run_pregel", run_pregel)


FAULTS = {"exchange_dropped": _exchange_dropped,
          "depths_altered": _depths_altered,
          "state_unchanged": _state_unchanged}


def rehearse(seed: int, control: bool = False, fault: str = None) -> dict:
    """One run of the cell without a chip, ``fault`` planted; run in a
    process with four devices."""
    mp = pytest.MonkeyPatch()
    try:
        if fault:
            FAULTS[fault](mp)
        return harness.run_cell(CELL, seed, 1.0, False, require_chip=False,
                                control=control, graph=GRAPH)
    finally:
        mp.undo()


SCRIPT = """
import json, sys
from benchmarks.chip import test_algo
for seed, control, fault in json.loads(sys.argv[1]):
    print("RESULT", json.dumps(test_algo.rehearse(seed, control, fault)),
          flush=True)
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    """Each run's result line and its window's compile count, in one
    process on four virtual CPU devices: two sound runs of different
    seeds (the second's set-up loads every program from the cache), the
    control, and each fault."""
    runs = [(2 ** 35 + 11, False, None), (2 ** 40 + 5, False, None),
            (2 ** 33 + 3, True, None)] + [
        (2 ** 33 + 3, False, f) for f in sorted(FAULTS)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path_factory.mktemp("jc")),
           "PYTHONPATH": os.pathsep.join([harness.ROOT, os.path.join(
               harness.ROOT, "src")])}
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, json.dumps(runs)], env=env,
        cwd=harness.ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.splitlines()
    window = [int(ln.split()[4]) for ln in lines
              if ln.startswith("compiles in the window:")]
    out = [json.loads(ln[len("RESULT "):]) for ln in lines
           if ln.startswith("RESULT ")]
    # the process's compiles so far, as each set-up ends
    setup = [int(ln.split()[3]) for ln in proc.stderr.splitlines()
             if ln.startswith("set-up ") and "compiles" in ln]
    return {"runs": runs, "results": out, "window": window, "setup": setup}


def test_sound_runs_are_correct(results):
    for r in results["results"][:2]:
        assert r["correct"], r["checks"]
        assert set(r["metrics"]) == {"algo_evps", "setup_s"}
        assert r["attempted"] % 3 == 0 and r["attempted"] > 0
        assert r["failed"] == 0
        assert r["device"]["count"] == 4
        assert r["checks"]["bfs_wrong"]["value"] == 0
        assert r["checks"]["wcc_wrong"]["value"] == 0
        assert r["checks"]["pagerank_gap"]["value"] < 1e-6


def test_nothing_compiles_in_the_window(results):
    assert results["window"] == [0] * len(results["runs"])
    # the second seed's set-up finds every program in the cache
    assert results["setup"][1] == results["setup"][0] > 0


def test_control_fails(results):
    r = results["results"][2]
    assert not r["correct"], r["checks"]
    assert r["checks"]["pagerank_gap"]["value"] > 3e-4
    assert r["checks"]["wcc_wrong"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_fails(results, fault):
    i = [f for _, _, f in results["runs"]].index(fault)
    r = results["results"][i]
    assert not r["correct"], r["checks"]
