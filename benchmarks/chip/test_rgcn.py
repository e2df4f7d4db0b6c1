"""The R-GCN cell's own files: the typed generator's counts, the operation
and byte counts, the scope its sparse update is read by, its metric
readers, and CPU rehearsals of ``ogbn_mag.rgcn_train`` at a tiny size,
with the control and each fault coming out not correct."""

import copy
import types

import jax
import numpy as np
import pytest

from benchmarks.chip import flops_rgcn, scopes
from benchmarks.chip.data import ogbn_mag
from benchmarks.chip.harness import HERE, load_json, seed_key

CELL = "ogbn_mag.rgcn_train"
CONFIG = load_json(f"{HERE}/configs/ogbn_mag.json")
NODES = {"paper": 800, "author": 900, "institution": 50,
         "field_of_study": 250}
EDGES = {"cites": 3000, "writes": 3000, "affiliated_with": 700,
         "has_topic": 3000}


def small_graph(**kw):
    edges = copy.deepcopy(CONFIG["graph"]["edge_types"])
    for e in edges:
        e["count"] = EDGES[e["name"]]
    return {**CONFIG["graph"], "node_types": NODES, "edge_types": edges,
            "feature_dim": 16, "n_classes": 8, **kw}


JOB = {"batch_size": 64, "hidden": 16, "fanouts": [5, 4]}


# ---------------------------------------------------------------- generator
def test_full_size_counts():
    g = CONFIG["graph"]
    assert ogbn_mag.n_arcs(g) == 42_222_014
    names, counts, _, edges = ogbn_mag.schema(g)
    assert sum(counts) == 1_939_743 and names[0] == "paper"
    assert sum(m for _, _, m, _, _ in edges) == 21_111_007
    assert sorted({r for e in edges for r in e[3:]}) == list(range(7))
    out = jax.eval_shape(lambda k: ogbn_mag._draw(
        k, counts, ogbn_mag.schema(g)[2], edges, g["feature_dim"],
        g["n_classes"]), seed_key(0))
    assert [(o.shape, o.dtype) for o in out] == [
        ((1_939_744,), np.int32), ((42_222_014,), np.int32),
        ((42_222_014,), np.int32), ((1_939_743,), np.int32),
        ((1_939_743, 128), np.float32), ((1_939_743,), np.int32)]


@pytest.fixture(scope="module")
def small():
    return ogbn_mag.generate(small_graph(), 2 ** 40 + 5)


def test_small_counts_by_type_and_relation(small):
    n, indptr = small["n"], small["indptr"]
    assert n == sum(NODES.values()) and small["counts"] == NODES
    assert indptr[0] == 0 and indptr[-1] == len(small["indices"]) == 19400
    assert indptr.dtype == np.int64 and small["indices"].dtype == np.int32
    offs = np.cumsum([0] + list(NODES.values()))
    types = small["types"]
    for t in range(4):
        assert np.all(types[offs[t]:offs[t + 1]] == t)
    row = np.repeat(np.arange(n), np.diff(indptr))
    nbr, rel = small["indices"], small["relations"]
    want = {0: 6000, 1: 3000, 2: 3000, 3: 700, 4: 700, 5: 3000, 6: 3000}
    assert dict(zip(*np.unique(rel, return_counts=True))) == want
    # (source type, target type) of each relation, the target the row
    ends = {0: (0, 0), 1: (1, 0), 2: (0, 1), 3: (1, 2), 4: (2, 1),
            5: (0, 3), 6: (3, 0)}
    for r, (s, t) in ends.items():
        assert np.all(types[nbr[rel == r]] == s)
        assert np.all(types[row[rel == r]] == t)
    # sorted by (row, neighbour, relation); every edge in both directions
    key = (row * n + nbr) * 7 + rel
    assert np.all(np.diff(key) >= 0)
    rev = {0: 0, 1: 2, 2: 1, 3: 4, 4: 3, 5: 6, 6: 5}
    back = (nbr.astype(np.int64) * n + row) * 7 + np.vectorize(rev.get)(rel)
    assert np.array_equal(np.sort(back), key)
    lab = small["vprops"]["label"]
    assert np.all(lab[types != 0] == 0) and 0 <= lab.min() <= lab.max() < 8
    assert small["vprops"]["feat"].shape == (n, 16)


def test_broad_types_are_skewed(small):
    """Fields of study (exponent 0.9: about 55 % expected) hold more of
    their arcs in their top tenth than papers (0.4: about 25 %) do."""
    deg = np.diff(small["indptr"])
    offs = np.cumsum([0] + list(NODES.values()))

    def top_share(t):
        d = np.sort(deg[offs[t]:offs[t + 1]])[::-1]
        return d[:len(d) // 10].sum() / d.sum()

    assert top_share(3) > top_share(0) + 0.15


def test_determinism_by_seed(small):
    again = ogbn_mag.generate(small_graph(), 2 ** 40 + 5)
    other = ogbn_mag.generate(small_graph(), 2 ** 40 + 6)
    for k in ("indices", "relations"):
        assert np.array_equal(small[k], again[k])
    assert np.array_equal(small["vprops"]["feat"], again["vprops"]["feat"])
    assert not np.array_equal(small["indices"], other["indices"])


# ----------------------------------------------------------- flops, scopes
def test_rgcn_flops_hand_count():
    # batch 2, fanouts (3, 2), widths 4 → 5 → 6, 2 relations:
    # layer 0 updates depths 0 and 1: (2 + 6) rows · 2 · 4 · 5 · 3 = 960
    # layer 1 updates depth 0: 2 rows · 2 · 5 · 6 · 3 = 360
    assert flops_rgcn.rgcn_forward_flops(2, (3, 2), (4, 5, 6), 2) == 1320
    assert flops_rgcn.rgcn_train_flops(2, (3, 2), (4, 5, 6), 2) == 3 * 1320


def test_rgcn_flops_cell_shape():
    f = flops_rgcn.rgcn_train_flops(1024, (25, 20), (128, 64, 349), 7)
    assert f == 3 * (26_624 * 2 * 128 * 64 * 8 + 1024 * 2 * 64 * 349 * 8)
    assert flops_rgcn.embed_update_bytes(10, 128) == 10 * 128 * 8


@pytest.mark.parametrize("path, scope", [
    ("jit(_device_step_fn)/embed.update/scatter-add", "embed"),
    ("jit(_device_step_fn)/embed.update/sort", "embed"),
    ("jit(_device_step_fn)/gather.types/jit(_take)/gather", "gather"),
    ("jit(_device_step_fn)/sample.hop1/rem", "sample"),
])
def test_scopes_of_the_rgcn_step(path, scope):
    assert scopes.scope_of(path) == scope


# ------------------------------------------------------------------ readers
def _run(**extra):
    cell = types.SimpleNamespace(config=CONFIG, mix=load_json(
        f"{HERE}/traffic/rgcn_train.json"))
    return types.SimpleNamespace(cell=cell, device_kind="TPU v5 lite",
                                 window_s=30.0, extra=extra,
                                 trace_summary=None)


def test_readers():
    from benchmarks.chip.harness import metric_reader

    mfu, _ = metric_reader("rgcn_step_mfu")
    run = _run(window_steps=600, embed_rows=30_000_000)
    want = 100 * 11_566_841_856 * 600 / 30 / 197e12
    assert mfu.read(run, None) == pytest.approx(want)
    roof, _ = metric_reader("embed_update_roofline")
    assert roof.read(run, None) is None          # no trace, no reading
    run.trace_summary = {"scope_s": {"sample": 9.0}}
    assert roof.read(run, None) is None          # no embed scope
    run.trace_summary = {"scope_s": {"embed": 2.0}}
    assert roof.read(run, None) == pytest.approx(
        100 * 30_000_000 * 1024 / 2.0 / 819e9)
    assert mfu.read(_run(), None) is None


# --------------------------------------------------------------- rehearsals
def _relation_dropped(monkeypatch):
    """Every arc sent under relation 0."""
    import jax.numpy as jnp

    import repro.engines.sample as sample

    orig = sample.split_codes

    def split(codes, r):
        nbr, rel = orig(codes, r)
        return nbr, jnp.where(rel >= 0, 0, rel)

    monkeypatch.setattr(sample, "split_codes", split)


def _embed_frozen(monkeypatch):
    """The embedding rows left as they were."""
    from repro.learning.trainer import RGCNTrainer

    orig = RGCNTrainer._embed_update

    def update(self, table, *a):
        return table, orig(self, table, *a)[1]

    monkeypatch.setattr(RGCNTrainer, "_embed_update", update)


FAULTS = {"relation_dropped": _relation_dropped,
          "embed_frozen": _embed_frozen}


def test_rgcn_train(rehearse):
    r = rehearse(CELL, graph=small_graph(), mix=JOB)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"train_steps_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    # the same arithmetic on the CPU: weights by round-off; the change of
    # a float32 row of size about 1 moved by about 1e-4 keeps about four
    # digits, so the embedding's change is compared to 1e-3
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert checks.pop("paper_rows_moved") == 0
    assert checks.pop("embed_change_gap") < 1e-3
    assert all(v < 1e-4 for v in checks.values()), checks


def test_rgcn_train_control_fails(rehearse):
    r = rehearse(CELL, graph=small_graph(), mix=JOB, control=True)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_rgcn_train_faults_fail(rehearse, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    r = rehearse(CELL, graph=small_graph(), mix=JOB)
    assert not r["correct"], r["checks"]
