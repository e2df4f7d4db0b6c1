"""Hypothesis property tests on system invariants."""

import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.distributed import compression
from repro.engines.grape import GrapeEngine, algorithms as alg
from repro.kernels import ref
from repro.models import rwkv6 as rk
from repro.storage.csr import CSRStore
from repro.storage.gart import GARTStore

SETTINGS = dict(max_examples=25, deadline=None,
                suppress_health_check=[hypothesis.HealthCheck.too_slow])


@st.composite
def edge_lists(draw, max_n=24, max_e=80):
    n = draw(st.integers(2, max_n))
    e = draw(st.integers(1, max_e))
    src = draw(hnp.arrays(np.int64, (e,), elements=st.integers(0, n - 1)))
    dst = draw(hnp.arrays(np.int64, (e,), elements=st.integers(0, n - 1)))
    return n, src, dst


class TestStorageProperties:
    @given(edge_lists())
    @settings(**SETTINGS)
    def test_csr_preserves_multiset(self, g):
        n, src, dst = g
        s = CSRStore(n, src, dst)
        indptr, indices = s.adjacency()
        assert len(indices) == len(src)
        got = sorted(zip(np.repeat(np.arange(n), np.diff(indptr)), indices))
        want = sorted(zip(src, dst))
        assert got == want

    @given(edge_lists(), st.integers(0, 5))
    @settings(**SETTINGS)
    def test_gart_snapshot_version_monotone(self, g, extra):
        n, src, dst = g
        half = len(src) // 2
        gart = GARTStore(n, src[:half], dst[:half])
        versions = [gart.write_version]
        for i in range(extra):
            versions.append(gart.add_edges([int(src[0])], [int(dst[0])]))
        snaps = [gart.snapshot(v).n_edges for v in versions]
        assert snaps == sorted(snaps)           # edges only grow with version

    @given(edge_lists())
    @settings(**SETTINGS)
    def test_csc_transpose_involution(self, g):
        n, src, dst = g
        s = CSRStore(n, src, dst)
        indptr, srcs = s.csc()
        got = sorted(zip(srcs, np.repeat(np.arange(n), np.diff(indptr))))
        want = sorted(zip(src, dst))
        assert got == want


class TestAnalyticsProperties:
    @given(edge_lists(max_n=16, max_e=48), st.integers(1, 3))
    @settings(**SETTINGS)
    def test_pagerank_sums_to_one(self, g, frags):
        n, src, dst = g
        eng = GrapeEngine(CSRStore(n, src, dst), n_frags=frags)
        pr = np.asarray(alg.pagerank(eng, max_steps=30))
        # dangling mass leaks in the simple formulation; bound instead
        assert 0 < pr.sum() <= 1.0 + 1e-3
        assert (pr >= 0).all()

    @given(edge_lists(max_n=16, max_e=48))
    @settings(**SETTINGS)
    def test_bfs_triangle_inequality(self, g):
        n, src, dst = g
        eng = GrapeEngine(CSRStore(n, src, dst), n_frags=1)
        d = np.asarray(alg.bfs(eng, source=0, max_steps=n + 1))
        # every edge (u,v): d[v] <= d[u] + 1
        finite = np.isfinite(d[src])
        assert (d[dst[finite]] <= d[src[finite]] + 1).all()


class TestCompressionProperties:
    @given(hnp.arrays(np.float32, st.integers(1, 4000),
                      elements=st.floats(-100, 100, width=32)))
    @settings(**SETTINGS)
    def test_int8_roundtrip_error_bound(self, x):
        g = jnp.asarray(x)
        out = np.asarray(compression.roundtrip_int8(g))
        # per-block error ≤ scale/2 = max|block|/254
        assert np.all(np.abs(out - x) <= np.abs(x).max() / 254 + 1e-6)

    @given(hnp.arrays(np.float32, st.integers(8, 2000),
                      elements=st.floats(-10, 10, width=32)),
           st.floats(0.05, 0.5))
    @settings(**SETTINGS)
    def test_topk_keeps_largest(self, x, frac):
        g = jnp.asarray(x)
        out = np.asarray(compression.topk_mask(g, frac))
        kept = out != 0
        if kept.any() and (~kept).any():
            assert np.abs(x)[kept].min() >= np.abs(x)[~kept].max() - 1e-6

    @given(hnp.arrays(np.float32, 256, elements=st.floats(-5, 5, width=32)))
    @settings(**SETTINGS)
    def test_error_feedback_telescopes(self, x):
        """Σ wire_t = Σ g_t − residual_T: EF never loses gradient mass."""
        g = jnp.asarray(x)
        res = jnp.zeros_like(g)
        wires = []
        for _ in range(4):
            wire, res = compression.ef_compress(g, res, kind="int8")
            wires.append(np.asarray(wire))
        total_wire = np.sum(wires, axis=0)
        np.testing.assert_allclose(total_wire + np.asarray(res),
                                   4 * x, rtol=1e-4, atol=1e-4)


@st.composite
def labeled_graphs(draw, max_n=20, max_e=60, n_vlabels=2, n_elabels=2):
    """Random labeled property multigraph (self loops and parallel edges
    included on purpose — the frontier path must count them identically)."""
    n = draw(st.integers(2, max_n))
    e = draw(st.integers(1, max_e))
    src = draw(hnp.arrays(np.int64, (e,), elements=st.integers(0, n - 1)))
    dst = draw(hnp.arrays(np.int64, (e,), elements=st.integers(0, n - 1)))
    vlab = draw(hnp.arrays(np.int32, (n,),
                           elements=st.integers(0, n_vlabels - 1)))
    elab = draw(hnp.arrays(np.int32, (e,),
                           elements=st.integers(0, n_elabels - 1)))
    credits = draw(hnp.arrays(np.int32, (n,), elements=st.integers(0, 9)))
    return CSRStore(n, src, dst, vertex_labels=vlab, edge_labels=elab,
                    vertex_props={"credits": credits})


@st.composite
def traversal_plans(draw, n_vlabels=2, n_elabels=2):
    """Random 1–3-hop linear match chain + head filter + terminal."""
    from repro.core.ir.dag import (Agg, BinExpr, Const, Expand, GroupCount,
                                   LogicalPlan, Param, Pred, Project,
                                   PropRef, Scan, Select, With)

    n_hops = draw(st.integers(1, 3))
    maybe_label = st.one_of(st.none(), st.integers(0, n_vlabels - 1))
    ops = [Scan("v0", draw(maybe_label), None)]
    head = "v0"
    for h in range(1, n_hops + 1):
        alias = f"v{h}"
        ops.append(Expand(
            src=head,
            edge_label=draw(st.one_of(st.none(),
                                      st.integers(0, n_elabels - 1))),
            direction=draw(st.sampled_from(["out", "in"])),
            edge=f"e{h}", fused_vertex=alias,
            vertex_label=draw(maybe_label)))
        head = alias
    threshold = draw(st.one_of(st.none(), st.integers(0, 9)))
    param_filter = draw(st.booleans())
    if threshold is not None:
        rhs = Param("t") if param_filter else Const(threshold)
        ops.append(Select(Pred(BinExpr(
            ">", PropRef(head, "credits"), rhs))))
    terminal = draw(st.sampled_from(["project", "group", "count"]))
    if terminal == "project":
        ops.append(Project(((PropRef(head, None), "out"),)))
    elif terminal == "group":
        ops.append(GroupCount(PropRef(head, None), "cnt"))
    else:
        ops.append(With((), (Agg("count", None, "k"),)))
        ops.append(Project(((PropRef("k", None), "k"),)))
    return LogicalPlan(ops), threshold


class TestTraversalDifferential:
    """The fragment frontier path (DESIGN.md §9) against the interpreter
    oracle over random graphs × random plans × fragment counts × batch
    sizes — the differential surface the hybrid execution stands on."""

    @staticmethod
    def _assert_bag_equal(ref, got):
        from conftest import assert_results_bag_equal
        assert_results_bag_equal(ref, got)

    @pytest.mark.parametrize("n_frags", [1, 2, 4])
    @given(labeled_graphs(), traversal_plans())
    @settings(**SETTINGS)
    def test_fragment_equals_interpreter(self, n_frags, store, plan_t):
        from repro.core.ir.codegen import execute_plan, lower_to_frontier
        from repro.engines.frontier import FragmentFrontierExecutor
        from repro.storage.lpg import PropertyGraph

        plan, threshold = plan_t
        pg = PropertyGraph(store)
        program = lower_to_frontier(plan)
        assert program is not None       # generator stays in supported IR
        params = {"t": threshold if threshold is not None else 0}
        ex = FragmentFrontierExecutor(pg, n_frags=n_frags)
        got = ex.execute(plan, [params])[0]
        self._assert_bag_equal(execute_plan(plan, pg, params=params), got)

    @pytest.mark.parametrize("batch", [1, 8])
    @given(labeled_graphs(max_n=12, max_e=36), traversal_plans())
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[hypothesis.HealthCheck.too_slow])
    def test_batched_queries_independent(self, batch, store, plan_t):
        """B queries in one [B, N] program == B solo interpreter runs."""
        from repro.core.ir.codegen import execute_plan
        from repro.engines.frontier import FragmentFrontierExecutor
        from repro.storage.lpg import PropertyGraph

        plan, _ = plan_t
        pg = PropertyGraph(store)
        params_list = [{"t": b % 10} for b in range(batch)]
        outs = FragmentFrontierExecutor(pg, n_frags=2).execute(
            plan, params_list)
        for params, got in zip(params_list, outs):
            self._assert_bag_equal(
                execute_plan(plan, pg, params=params), got)


@pytest.mark.slow
class TestVarlenProperties:
    """Variable-length expansion + shortestPath (DESIGN.md §13) against
    the interpreter oracle on random multigraphs × random bounds —
    including min == 0 (identity term), min == max (single power), and
    max beyond any small graph's diameter (saturated reachability).
    Slow-marked (every (min, max) pair is a fresh unrolled jit); CI runs
    it derandomized in the `-m slow` job."""

    @staticmethod
    def _assert_bag_equal(ref, got):
        from conftest import assert_results_bag_equal
        assert_results_bag_equal(ref, got)

    @given(labeled_graphs(max_n=14, max_e=40),
           st.integers(0, 3), st.integers(0, 14),
           st.sampled_from([1, 2, 4]), st.sampled_from(["out", "in"]),
           st.booleans())
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[hypothesis.HealthCheck.too_slow])
    def test_expand_var_equals_interpreter(self, store, lo, extra, n_frags,
                                           direction, filtered):
        """max = min + extra may exceed the diameter; walk counts on the
        fragment route must still match the interpreter exactly."""
        from repro.core.ir.codegen import execute_plan, lower_to_frontier
        from repro.core.ir.dag import (BinExpr, Const, ExpandVar,
                                       LogicalPlan, Pred, Project, PropRef,
                                       Scan, Select)
        from repro.engines.frontier import FragmentFrontierExecutor
        from repro.storage.lpg import PropertyGraph

        lo = max(lo, 0)
        hi = max(lo, min(lo + extra, 14))
        if hi == 0 and lo == 0:
            hi = 1
            lo = 0
        pg = PropertyGraph(store)
        ops = [Scan("a", None, None),
               ExpandVar(src="a", alias="b", edge_label=0,
                         direction=direction, min_hops=lo, max_hops=hi)]
        if filtered:
            ops.append(Select(Pred(BinExpr(
                ">", PropRef("b", "credits"), Const(4)))))
        ops.append(Project(((PropRef("b", None), "b"),)))
        plan = LogicalPlan(ops)
        assert lower_to_frontier(plan) is not None
        ex = FragmentFrontierExecutor(pg, n_frags=n_frags)
        if self._exact_walk_peak(store, direction, lo, hi) >= 2 ** 24:
            # float32 counts are inexact past 2^24: the fragment route
            # must refuse (the service reruns on the interpreter)
            with pytest.raises(OverflowError):
                ex.execute(plan, [None])
            return
        got = ex.execute(plan, [None])[0]
        self._assert_bag_equal(execute_plan(plan, pg), got)

    @staticmethod
    def _exact_walk_peak(store, direction, lo, hi):
        """Largest per-vertex walk count any powered stage of the label-0
        expansion reaches from the all-vertex scan, in exact integers."""
        n = store.n_vertices
        indptr, indices = store.adjacency()
        sel = store.edge_labels() == 0
        src = np.repeat(np.arange(n), np.diff(indptr))[sel]
        dst = indices[sel]
        if direction == "in":
            src, dst = dst, src
        adj = np.zeros((n, n), dtype=object)
        np.add.at(adj, (src, dst), 1)
        cur = np.ones(n, dtype=object)
        acc = cur.copy() if lo == 0 else np.zeros(n, dtype=object)
        peak = 0
        for k in range(1, hi + 1):
            cur = cur.dot(adj)
            peak = max(peak, max(cur))
            if k >= lo:
                acc = acc + cur
        return max(peak, max(acc))

    @given(labeled_graphs(max_n=14, max_e=40),
           st.integers(0, 1), st.integers(1, 10),
           st.sampled_from([1, 2, 4]), st.booleans())
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[hypothesis.HealthCheck.too_slow])
    def test_shortest_equals_interpreter(self, store, lo, hi0, n_frags,
                                         filtered):
        """Bounded shortestPath distances (and which pairs appear at all)
        match the interpreter, unreachable pairs stay absent."""
        from repro.core.ir.codegen import execute_plan, lower_to_frontier
        from repro.core.ir.dag import (BinExpr, Const, LogicalPlan, Pred,
                                       Project, PropRef, Scan, Select,
                                       ShortestPath)
        from repro.engines.frontier import FragmentFrontierExecutor
        from repro.storage.lpg import PropertyGraph

        hi = max(hi0, lo, 1)
        pg = PropertyGraph(store)
        ops = [Scan("a", None, None),
               ShortestPath(src="a", alias="b", edge_label=0,
                            direction="out", min_hops=lo, max_hops=hi)]
        if filtered:
            ops.append(Select(Pred(BinExpr(
                ">", PropRef("b", "credits"), Const(4)))))
        ops.append(Project(((PropRef("a", None), "a"),
                            (PropRef("b", None), "b"),
                            (PropRef("dist", None), "d"))))
        plan = LogicalPlan(ops)
        assert lower_to_frontier(plan) is not None
        got = FragmentFrontierExecutor(pg, n_frags=n_frags).execute(
            plan, [None])[0]
        self._assert_bag_equal(execute_plan(plan, pg), got)


class TestRWKVProperties:
    @given(st.integers(1, 2), st.integers(1, 3), st.integers(8, 16))
    @settings(max_examples=10, deadline=None)
    def test_chunked_equals_sequential(self, B, H, P):
        S = 32
        rng = np.random.default_rng(B * 100 + H * 10 + P)
        r, k, v = (jnp.asarray(rng.standard_normal((B, S, H, P)),
                               jnp.float32) for _ in range(3))
        lw = jnp.asarray(-np.abs(rng.standard_normal((B, S, H, P))) - 0.01,
                         jnp.float32)
        u = jnp.asarray(rng.standard_normal((H, P)), jnp.float32)
        s0 = jnp.zeros((B, H, P, P), jnp.float32)
        y_chunk, st_chunk = rk._wkv_chunked(r, k, v, lw, u, s0, chunk=8)
        y_seq, st_seq = ref.wkv_ref(r, k, v, lw, u, s0)
        np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(st_chunk), np.asarray(st_seq),
                                   rtol=2e-4, atol=2e-4)


class TestSSDProperties:
    @given(st.integers(1, 2), st.integers(1, 2), st.integers(4, 8))
    @settings(max_examples=10, deadline=None)
    def test_chunked_equals_sequential(self, B, H, N):
        from repro.models.mamba2 import _ssd_scan
        S, P, Q = 24, 8, 8
        rng = np.random.default_rng(B * 7 + H * 3 + N)
        xh = jnp.asarray(rng.standard_normal((B, S, H, P)), jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
        a = jnp.asarray(-np.abs(rng.standard_normal((B, S, H))) * 0.5,
                        jnp.float32)
        s0 = jnp.zeros((B, H, P, N), jnp.float32)
        y_c, st_c = _ssd_scan(xh.reshape(B, S // Q, Q, H, P),
                              Bm.reshape(B, S // Q, Q, N),
                              Cm.reshape(B, S // Q, Q, N),
                              a.reshape(B, S // Q, Q, H), s0)
        y_s, st_s = ref.ssd_ref(xh, Bm, Cm, a, s0)
        np.testing.assert_allclose(np.asarray(y_c).reshape(B, S, H, P),
                                   np.asarray(y_s), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(st_c), np.asarray(st_s),
                                   rtol=2e-4, atol=2e-4)


@st.composite
def sampler_graphs(draw, max_n=20, max_e=50):
    """Random graph with three pinned vertices for the sampling edge cases:
    vertex n-1 is ISOLATED, vertex n-2's only out-edge points at vertex 0
    (the edges-into-vertex-0 PAD regression), and general edges run among
    the rest."""
    n = draw(st.integers(4, max_n))
    e = draw(st.integers(1, max_e))
    src = draw(hnp.arrays(np.int64, (e,), elements=st.integers(0, n - 3)))
    dst = draw(hnp.arrays(np.int64, (e,), elements=st.integers(0, n - 3)))
    src = np.concatenate([src, [n - 2]])
    dst = np.concatenate([dst, [0]])
    rng = np.random.default_rng(n * 31 + e)
    feats = rng.standard_normal((n, 3)).astype(np.float32)
    return CSRStore(n, src, dst, vertex_props={"feat": feats}), feats


@pytest.mark.slow
class TestSamplerProperties:
    """Device-sampler edge cases (ISSUE 4): PAD isolation, vertex-0 edges
    under ELL padding, with-replacement draws below degree, empty batches —
    each against the numpy oracle walk on random graphs. Slow-marked (many
    executor builds ⇒ many jit compiles); CI runs it in the `-m slow` job
    next to the statistical sampler suite."""

    @given(sampler_graphs(), st.sampled_from([1, 2, 4]),
           st.sampled_from([1, 4, 15]),
           st.sampled_from(["stacked", "psum"]))
    @settings(**SETTINGS)
    def test_matches_oracle_walk(self, g, n_frags, fanout, exchange):
        from repro.engines.sample import FragmentSampleExecutor
        from repro.kernels.ref import sampler_ref
        from repro.kernels.sampler import csr_to_sample_ell, layer_uniforms

        store, _ = g
        ex = FragmentSampleExecutor(store, n_frags=n_frags,
                                    exchange=exchange)
        key = jax.random.PRNGKey(store.n_vertices)
        seeds = np.arange(store.n_vertices, dtype=np.int32)
        layers, _, _ = ex.sample(seeds, key, (fanout,))
        indptr, indices = store.adjacency()
        ell, deg = csr_to_sample_ell(indptr, indices)
        u = np.asarray(layer_uniforms(key, 0, len(seeds), fanout))
        np.testing.assert_array_equal(np.asarray(layers[0]),
                                      sampler_ref(ell, deg, seeds, u))

    @given(sampler_graphs(), st.sampled_from([1, 2, 4]))
    @settings(**SETTINGS)
    def test_isolated_vertex_stays_pad(self, g, n_frags):
        from repro.engines.sample import FragmentSampleExecutor

        store, feats = g
        n = store.n_vertices
        ex = FragmentSampleExecutor(store, n_frags=n_frags)
        seeds = np.array([n - 1, -1], np.int32)   # isolated + explicit PAD
        layers, fts, _ = ex.sample(seeds, jax.random.PRNGKey(0), (4, 2))
        assert (np.asarray(layers[0]) == -1).all()
        assert (np.asarray(layers[1]) == -1).all()
        # the isolated vertex still has features; PAD rows are zero
        np.testing.assert_array_equal(np.asarray(fts[0][0]), feats[n - 1])
        assert (np.asarray(fts[0][1]) == 0).all()
        assert (np.asarray(fts[1]) == 0).all()

    @given(sampler_graphs(), st.sampled_from([1, 2, 4]),
           st.sampled_from([1, 4, 15]))
    @settings(**SETTINGS)
    def test_edges_into_vertex_zero_survive(self, g, n_frags, fanout):
        """deg(n-2) == 1 with its single neighbor being vertex 0: every
        draw must be 0 — if ELL padding corrupted id 0 these would come
        back PAD_SENTINEL."""
        from repro.engines.sample import FragmentSampleExecutor

        store, _ = g
        n = store.n_vertices
        ex = FragmentSampleExecutor(store, n_frags=n_frags)
        seeds = np.full(3, n - 2, np.int32)
        layers, _, _ = ex.sample(seeds, jax.random.PRNGKey(1), (fanout,))
        assert (np.asarray(layers[0]) == 0).all()

    @given(sampler_graphs(), st.sampled_from([4, 15]))
    @settings(**SETTINGS)
    def test_below_degree_resolves_with_replacement(self, g, fanout):
        """Whenever deg < fanout the draw is with-replacement: every slot
        of a non-isolated seed is a valid neighbor, never PAD."""
        from repro.engines.sample import FragmentSampleExecutor

        store, _ = g
        indptr, indices = store.adjacency()
        deg = np.diff(indptr)
        ex = FragmentSampleExecutor(store, n_frags=2)
        seeds = np.arange(store.n_vertices, dtype=np.int32)
        layers, _, _ = ex.sample(seeds, jax.random.PRNGKey(2), (fanout,))
        out = np.asarray(layers[0])
        for v in range(store.n_vertices):
            if deg[v] == 0:
                assert (out[v] == -1).all()
                continue
            assert (out[v] >= 0).all()            # replacement fills fanout
            nbrs = set(indices[indptr[v]:indptr[v + 1]].tolist())
            assert set(out[v].tolist()) <= nbrs

    @given(sampler_graphs(), st.sampled_from(["stacked", "psum"]))
    @settings(**SETTINGS)
    def test_empty_seed_batch(self, g, exchange):
        from repro.engines.sample import FragmentSampleExecutor

        store, _ = g
        ex = FragmentSampleExecutor(store, n_frags=2, exchange=exchange)
        layers, fts, _ = ex.sample(np.zeros((0,), np.int32),
                                   jax.random.PRNGKey(0), (4, 2))
        assert [tuple(l.shape) for l in layers] == [(0, 4), (0, 2)]
        assert [tuple(f.shape) for f in fts] == [(0, 3), (0, 3), (0, 3)]
