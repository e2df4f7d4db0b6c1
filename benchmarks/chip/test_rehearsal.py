"""CPU rehearsals of the GraphSAGE training cell through the harness at a
tiny size: the result line's keys, the refusal without a chip, the
program matching the plain reference, the control and each fault a
training cell on one chip can have coming out not correct, and a cell,
mix and metric added by files alone."""

import json
import os
import shutil
import sys

import pytest

from benchmarks.chip import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
GRAPH = {"n_nodes": 2000, "n_undirected_edges": 16000, "feature_dim": 8}

CELL = "ogbn_products.sage_train"
JOB = {"batch_size": 64, "hidden": 32}


def test_cli_refuses_without_a_chip(capsys):
    assert harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                         "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_sage_train(rehearse):
    r = rehearse(CELL, graph=GRAPH, mix=JOB)
    assert list(r) == KEYS
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"train_steps_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    # the same arithmetic on the CPU: the gaps are round-off
    assert all(c["value"] < 1e-4 for c in r["checks"].values())


def test_sage_train_control_fails(rehearse):
    assert not rehearse(CELL, graph=GRAPH, mix=JOB, control=True)["correct"]


def _state_unchanged(monkeypatch):
    from repro.learning.trainer import SageTrainer

    orig = SageTrainer._device_step_fn

    def step(self, params, tables, s, seeds):
        return params, orig(self, params, tables, s, seeds)[1]

    monkeypatch.setattr(SageTrainer, "_device_step_fn", step)


def _half_batch(monkeypatch):
    from repro.learning.trainer import SageTrainer

    orig = SageTrainer._device_step_fn

    def step(self, params, tables, s, seeds):
        return orig(self, params, tables, s, seeds[:seeds.shape[0] // 2])

    monkeypatch.setattr(SageTrainer, "_device_step_fn", step)


def _draws_altered(monkeypatch):
    import repro.engines.sample as sample

    orig = sample.layer_uniforms
    monkeypatch.setattr(sample, "layer_uniforms",
                        lambda *a: 1.0 - orig(*a) * 0.999)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "draws_altered": _draws_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_sage_train_faults_fail(rehearse, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    r = rehearse(CELL, graph=GRAPH, mix=JOB)
    assert not r["correct"], r["checks"]


def test_cell_mix_and_metric_added_by_files(rehearse, tmp_path):
    """A new cell with its own mix and its own per-layer metric, added as
    files to a copy of the checkout and entries in its BENCHMARK.json:
    the harness finds all three by name."""
    root = tmp_path / "checkout"
    here = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "test*"))
    bench = json.loads(open(os.path.join(harness.ROOT,
                                         "BENCHMARK.json")).read())
    mix = json.loads((here / "traffic" / "sage_train.json").read_text())
    mix.update(fanouts=[10, 5], batch_size=32)
    (here / "traffic" / "sage_two_hop.json").write_text(json.dumps(mix))
    (here / "metrics" / "steps_done.py").write_text(
        "def read(run, suffix):\n"
        "    return float(run.extra['window_steps'])\n")
    cell = "ogbn_products.sage_two_hop"
    bench["workloads"].append({"name": cell, "config": "ogbn_products",
                               "traffic": "sage_two_hop", "chips": 1,
                               "why": "two hops"})
    bench["per_layer"].append({"name": "steps_done", "unit": "steps",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "learning",
                               "moves": "train_steps_per_s",
                               "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = rehearse(cell, graph=GRAPH, trace=True, root=str(root))
    sys.modules.pop("benchmarks.chip.metrics.steps_done", None)
    assert r["correct"], r["checks"]
    assert r["metrics"] == {"steps_done": {"value": float(r["attempted"]),
                                           "unit": "steps"}}
