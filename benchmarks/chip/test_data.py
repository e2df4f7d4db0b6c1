"""The benchmark's graph generator: the configuration's sizes, the CSR
form the store adopts, symmetry, skew and determinism by seed."""

import jax
import numpy as np

from benchmarks.chip.data import chung_lu
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
