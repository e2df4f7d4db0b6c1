"""GRAPE analytics: Pregel/PIE/FLASH algorithms vs numpy oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.engines.grape import GrapeEngine, algorithms as alg
from repro.engines.grape.engine import LANES
from repro.storage.generators import rmat_store
from repro.storage.csr import CSRStore
from repro.storage.partition import PAD_SENTINEL, partition


@pytest.fixture(scope="module")
def graph():
    return rmat_store(scale=8, edge_factor=8, seed=3)


@pytest.fixture(scope="module", params=[1, 4])
def engine(request, graph):
    return GrapeEngine(graph, n_frags=request.param)


class TestPregel:
    def test_pagerank_matches_numpy(self, graph, engine):
        pr = np.asarray(alg.pagerank(engine, max_steps=30))
        indptr, indices = graph.adjacency()
        ref = alg.pagerank_numpy(indptr, indices, iters=30)
        np.testing.assert_allclose(pr, ref, rtol=1e-4, atol=1e-7)

    def test_memoized_fixpoint_honours_its_bounds(self, graph):
        """A later call with other loop bounds must not reuse the first
        call's compiled fixpoint."""
        eng = GrapeEngine(graph)
        short = np.asarray(alg.pagerank(eng, max_steps=2, tol=0.0))
        full = np.asarray(alg.pagerank(eng, max_steps=30))
        fresh = np.asarray(alg.pagerank(GrapeEngine(graph), max_steps=30))
        np.testing.assert_array_equal(full, fresh)
        assert not np.array_equal(short, full)

    def test_pagerank_fragments_invariant(self, graph):
        e1 = GrapeEngine(graph, n_frags=1)
        e3 = GrapeEngine(graph, n_frags=3)
        p1 = np.asarray(alg.pagerank(e1, max_steps=20))
        p3 = np.asarray(alg.pagerank(e3, max_steps=20))
        np.testing.assert_allclose(p1, p3, rtol=1e-5, atol=1e-8)

    def test_bfs_matches_numpy(self, graph, engine):
        d = np.asarray(alg.bfs(engine, source=0))
        indptr, indices = graph.adjacency()
        ref = alg.bfs_numpy(indptr, indices, 0)
        np.testing.assert_array_equal(d, ref.astype(np.float32))

    def test_sssp_matches_numpy(self, graph, engine):
        d = np.asarray(alg.sssp(engine, source=0))
        indptr, indices = graph.adjacency()
        w = graph.edge_prop("weight")
        ref = alg.sssp_numpy(indptr, indices, w, 0)
        np.testing.assert_allclose(d, ref, rtol=1e-4, atol=1e-5)

    def test_wcc_valid_partition(self, engine, graph):
        # symmetrize first for true weak components
        indptr, indices = graph.adjacency()
        src = np.repeat(np.arange(graph.n_vertices), np.diff(indptr))
        s2 = CSRStore(graph.n_vertices,
                      np.concatenate([src, indices]),
                      np.concatenate([indices, src]))
        e = GrapeEngine(s2, n_frags=2)
        lab = np.asarray(alg.wcc(e, max_steps=64))
        ip, ix = s2.adjacency()
        s_arr = np.repeat(np.arange(s2.n_vertices), np.diff(ip))
        assert (lab[s_arr] == lab[ix]).all()   # endpoints share a component


class TestPIE:
    def test_pie_pagerank_equals_pregel(self, graph):
        e = GrapeEngine(graph, n_frags=2)
        a = np.asarray(alg.pagerank(e, max_steps=25))
        b = np.asarray(alg.pagerank_pie(e, rounds=25))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


class TestFLASH:
    def test_kcore_definition(self, graph):
        e = GrapeEngine(graph, n_frags=2)
        k = 4
        alive = np.asarray(alg.kcore(e, k=k))
        # within the returned core, every vertex has >= k in-core in-edges
        indptr, indices = graph.adjacency()
        src = np.repeat(np.arange(graph.n_vertices), np.diff(indptr))
        deg_in_core = np.zeros(graph.n_vertices)
        m = alive[src]  # only edges from alive sources count
        np.add.at(deg_in_core, indices[m], 1)
        assert (deg_in_core[alive] >= k).all()

    def test_cc_pointer_jumping_valid(self, graph):
        indptr, indices = graph.adjacency()
        src = np.repeat(np.arange(graph.n_vertices), np.diff(indptr))
        s2 = CSRStore(graph.n_vertices,
                      np.concatenate([src, indices]),
                      np.concatenate([indices, src]))
        e = GrapeEngine(s2, n_frags=2)
        lab = np.asarray(alg.cc_pointer_jumping(e))
        ip, ix = s2.adjacency()
        s_arr = np.repeat(np.arange(s2.n_vertices), np.diff(ip))
        assert (lab[s_arr] == lab[ix]).all()

    def test_equity_analysis_case(self):
        # the paper's §8 example: Person C holds 0.8*0.6 + 0.8*0.3*0.7 = 0.648
        #   C -> Co2 (0.8), C -> Co3 (0.8)?  — build the figure's graph:
        # PersonC -0.8-> Co2 -0.6-> Co1 ; PersonC -0.8-> Co3? figure: C owns
        # Co2 80%; Co2 owns Co1 60%; C owns Co3 via ... we model:
        # C -0.8-> Co2, Co2 -0.6-> Co1, Co2 -0.3-> Co3, Co3 -0.7-> Co1
        src = np.array([3, 0, 0, 1])
        dst = np.array([0, 2, 1, 2])
        w = np.array([0.8, 0.6, 0.3, 0.7], np.float32)
        # vertices: 0=Co2, 1=Co3, 2=Co1, 3=PersonC
        store = CSRStore(4, src, dst, edge_props={"weight": w})
        e = GrapeEngine(store, n_frags=1)
        holder = np.array([0, 0, 0, 1], np.float32)   # PersonC is the holder
        share = np.asarray(alg.equity_shares(e, holder, max_steps=10))
        np.testing.assert_allclose(share[2], 0.8 * 0.6 + 0.8 * 0.3 * 0.7,
                                   rtol=1e-5)


# A directed multigraph for the superstep's run layout: targets 0 and 1
# take exactly LANES arcs, all from sources in the first fragment, so the
# hub's run starts on a row boundary there; the hub (vertex 2) takes an
# arc from every source in 3 … ISOLATED-1, a run over several rows in
# each fragment; vertices ISOLATED … N_RUNS-1 have no arcs; fragments of
# unequal arc counts pad the shorter ones.
N_RUNS, HUB, ISOLATED = 1000, 2, 990


@pytest.fixture(scope="module")
def runs_graph():
    rng = np.random.default_rng(7)
    src = [np.arange(10, 10 + LANES), np.arange(3, ISOLATED),
           rng.integers(3, ISOLATED, 3000)]
    dst = [np.arange(LANES) % 2, np.full(ISOLATED - 3, HUB),
           rng.integers(HUB, ISOLATED, 3000)]
    src, dst = np.concatenate(src), np.concatenate(dst)
    w = rng.uniform(0.5, 2.0, len(src)).astype(np.float32)
    return CSRStore(N_RUNS, src, dst, edge_props={"weight": w})


@pytest.fixture(scope="module", params=[1, 3])
def runs_engine(request, runs_graph):
    return GrapeEngine(runs_graph, n_frags=request.param)


_ORACLES = {"sum": (np.add, 0.0), "min": (np.minimum, np.inf),
            "max": (np.maximum, -np.inf)}


class TestSuperstepRuns:
    @pytest.mark.parametrize("weighted", [False, True])
    @pytest.mark.parametrize("combiner", ["sum", "min", "max"])
    def test_superstep_matches_numpy(self, runs_graph, runs_engine,
                                     combiner, weighted):
        """The segmented combine over target runs against ``np.<op>.at``
        over the store's arcs; min and max exact, sum within 1e-6."""
        fa = runs_engine.frags
        assert not np.asarray(fa.e_mask).all() or runs_engine.n_frags == 1
        vals = np.random.default_rng(1).uniform(
            0.1, 1.0, N_RUNS).astype(np.float32)
        got = np.asarray(runs_engine.superstep(
            runs_engine.owned_view(jnp.asarray(vals)), combiner, weighted))
        indptr, indices = runs_graph.adjacency()
        src = np.repeat(np.arange(N_RUNS), np.diff(indptr))
        msg = vals[src]
        if weighted:
            w = runs_graph.edge_prop("weight")
            msg = msg * w if combiner == "sum" else msg + w
        op, identity = _ORACLES[combiner]
        want = np.full(N_RUNS, identity,
                       np.float64 if combiner == "sum" else np.float32)
        op.at(want, indices, msg)
        assert np.isinf(got[ISOLATED:]).all() or combiner == "sum"
        if combiner == "sum":
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
        else:
            np.testing.assert_array_equal(got, want)

    def test_hub_run_starts_on_a_row_boundary(self, runs_engine):
        """The case the oracle test must hold: the hub's run starts at a
        multiple of LANES and spans several rows."""
        fa = runs_engine.frags
        idx, mask = np.asarray(fa.indices[0]), np.asarray(fa.e_mask[0])
        run = np.flatnonzero(mask & (idx == HUB))
        assert run[0] == LANES and len(run) > 2 * LANES

    def test_prepare_groups_arcs_by_target(self, runs_graph, runs_engine):
        """Each fragment holds the arcs ``partition()`` gave it, targets
        ascending with sources ascending inside a run and pad arcs last,
        and each target's last-arc position and presence agree with them."""
        parts = partition(runs_graph, runs_engine.n_frags)
        fa = runs_engine.frags
        for f in range(runs_engine.n_frags):
            ptr, real = parts.indptr[f], parts.indices[f] != PAD_SENTINEL
            want_src = np.repeat(np.arange(parts.v_per_frag), np.diff(ptr))
            want = sorted(zip(want_src, parts.indices[f][real],
                              parts.weights[f][real]))
            idx = np.asarray(fa.indices[f])
            src = np.asarray(fa.e_src[f])
            mask = np.asarray(fa.e_mask[f])
            w = np.asarray(fa.weights[f])
            k = int(mask.sum())
            assert mask[:k].all() and not mask[k:].any()   # pads last
            assert sorted(zip(src[:k], idx[:k], w[:k])) == want
            assert (np.diff(idx) >= 0).all()
            same = idx[1:k] == idx[:k - 1]
            assert (src[1:k][same] >= src[:k - 1][same]).all()
            last, has = np.asarray(fa.run_last[f]), np.asarray(fa.run_has[f])
            ends = np.flatnonzero(np.append(idx[1:k] != idx[:k - 1], True))
            want_has = np.zeros(N_RUNS, bool)
            want_has[idx[ends]] = True
            np.testing.assert_array_equal(has, want_has)
            np.testing.assert_array_equal(last[idx[ends]], ends)
