"""The benchmark's graph generator: the configuration's sizes, the CSR
form the store adopts, symmetry, skew and determinism by seed."""

import jax
import numpy as np

from benchmarks.chip.data import chung_lu, graph500
from benchmarks.chip.harness import HERE, load_json, seed_key

SMALL = {"n_nodes": 3000, "n_undirected_edges": 40000, "feature_dim": 6,
         "n_classes": 47, "degree_exponent": 0.4}


def test_ogbn_products_sizes():
    g = load_json(f"{HERE}/configs/ogbn_products.json")["graph"]
    out = jax.eval_shape(
        lambda k: chung_lu._draw(k, g["n_nodes"], g["n_undirected_edges"],
                                 g["degree_exponent"], g["feature_dim"],
                                 g["n_classes"]), seed_key(0))
    assert [(o.shape, o.dtype) for o in out] == [
        ((2_449_030,), np.int32), ((123_718_280,), np.int32),
        ((2_449_029, 100), np.float32), ((2_449_029,), np.int32)]


def test_csr_symmetric_and_sorted():
    ds = chung_lu.generate(SMALL, 2 ** 40 + 3)
    n, indptr, indices = ds["n"], ds["indptr"], ds["indices"]
    assert indptr[0] == 0 and indptr[-1] == len(indices) == 80000
    assert indptr.dtype == np.int64 and indices.dtype == np.int32
    src = np.repeat(np.arange(n), np.diff(indptr))
    assert np.all(np.diff(src * n + indices) >= 0)
    arcs = np.sort(src * n + indices)
    assert np.array_equal(arcs, np.sort(indices.astype(np.int64) * n + src))
    assert ds["vprops"]["feat"].shape == (n, 6)
    assert ds["vprops"]["feat"].dtype == np.float32
    assert 0 <= ds["vprops"]["label"].min() <= ds["vprops"]["label"].max() < 47


def test_degree_skew():
    deg = np.diff(chung_lu.generate(SMALL, 5)["indptr"])
    # weight (r + 1) ** -0.4: the top 1% of vertices hold about 6% of arcs
    top = np.sort(deg)[::-1][:30].sum() / deg.sum()
    assert 0.045 < top < 0.08
    assert deg.max() > 4 * np.median(deg)


def test_determinism_by_seed():
    a, b = chung_lu.generate(SMALL, 2 ** 33 + 1), \
        chung_lu.generate(SMALL, 2 ** 33 + 1)
    c = chung_lu.generate(SMALL, 2 ** 33 + 2)
    assert np.array_equal(a["indices"], b["indices"])
    assert np.array_equal(a["vprops"]["feat"], b["vprops"]["feat"])
    assert not np.array_equal(a["indices"], c["indices"])
    assert not np.array_equal(a["vprops"]["feat"], c["vprops"]["feat"])


# Graph500 at scale 10 on the one device a test process has
G500 = {"scale": 10, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "n_edges": 10000, "draw_slack": 0.02, "fragments": 1,
        "structure_seed": 7}


def test_graph500_22_sizes():
    g = load_json(f"{HERE}/configs/graph500_22.json")["graph"]
    assert 2 ** g["scale"] == 4_194_304
    # one edge fewer than the dataset's 64,155,735: equal fragments
    assert g["n_edges"] == 64_155_735 - 1
    assert 2 * g["n_edges"] // g["fragments"] == 32_077_867
    assert (2 * g["n_edges"]) % g["fragments"] == 0
    # the draw has room for the distinct edges it has to keep
    assert g["edge_factor"] * 2 ** g["scale"] * (1 + g["draw_slack"]) \
        > 1.06 * g["n_edges"]


def test_graph500_symmetric_without_loops_or_repeats():
    ds = graph500.generate(G500, 2 ** 40 + 3)
    n, indptr, indices = ds["n"], ds["indptr"], ds["indices"]
    assert n == 1024 and ds["n_edges"] == 10000
    assert indptr[0] == 0 and indptr[-1] == len(indices) == 20000
    assert indptr.dtype == np.int64 and indices.dtype == np.int32
    src = np.repeat(np.arange(n), np.diff(indptr))
    arcs = src * n + indices
    assert np.all(np.diff(arcs) > 0)            # sorted, no repeated arc
    assert not np.any(src == indices)           # no self-loop
    assert np.array_equal(arcs, np.sort(indices.astype(np.int64) * n + src))
    deg = np.diff(indptr)
    assert ds["source"] == int(np.argmax(deg))
    # Kronecker skew: the top 1% of vertices hold a tenth of the arcs
    assert np.sort(deg)[::-1][:10].sum() / deg.sum() > 0.1


def test_graph500_determinism_by_seed():
    a, b = graph500.generate(G500, 2 ** 33 + 1), \
        graph500.generate(G500, 2 ** 33 + 1)
    c = graph500.generate(G500, 2 ** 33 + 2)
    assert np.array_equal(a["indptr"], b["indptr"])
    assert np.array_equal(a["indices"], b["indices"])
    assert not np.array_equal(a["indices"], c["indices"])
    # one graph under another naming: the same degrees, the hub named 0
    assert np.array_equal(np.sort(np.diff(a["indptr"])),
                          np.sort(np.diff(c["indptr"])))
    assert a["source"] == c["source"] == 0


def test_hub_first_keeps_fragments():
    rng = np.random.default_rng(5)
    deg = rng.zipf(1.8, 4096).clip(max=500)
    deg[rng.random(4096) < 0.4] = 0
    deg[0] += -deg.sum() % 4
    deg[3000] = 600                              # the hub
    perm = graph500.balanced_perm(deg, 4, np.random.default_rng(3))
    moved = graph500.hub_first(perm.copy(), deg, 1024)
    assert moved[3000] == 0
    assert np.array_equal(np.sort(moved), np.arange(4096))
    assert np.array_equal(np.bincount(moved // 1024, weights=deg),
                          np.bincount(perm // 1024, weights=deg))


def test_balanced_perm_evens_fragments():
    rng = np.random.default_rng(7)
    deg = rng.zipf(1.8, 4096).clip(max=500)
    deg[rng.random(4096) < 0.4] = 0
    deg[0] += -deg.sum() % 4
    perm = graph500.balanced_perm(deg, 4, np.random.default_rng(3))
    assert np.array_equal(np.sort(perm), np.arange(4096))
    load = np.bincount(perm // 1024, weights=deg, minlength=4)
    assert np.all(load == deg.sum() // 4)
