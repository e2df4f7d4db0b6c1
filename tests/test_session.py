"""FlexSession + write route (DESIGN.md §11): mutation IR, snapshot-pinned
flush semantics against a numpy oracle across F ∈ {1, 2, 4} fragment
routing, the version-epoch invalidation bus, time-travel reads, and the
four-verbs acceptance criterion."""

import numpy as np
import pytest
from conftest import assert_results_bag_equal

from repro.core.ir.cbo import (Catalog, is_point_lookup,
                               should_use_fragment_path)
from repro.core.ir.codegen import execute_plan
from repro.core.ir.dag import (InsertEdge, LogicalPlan, Scan, SetProp,
                               plan_is_write)
from repro.core.ir.parser import parse_cypher, parse_gremlin
from repro.core.ir.rbo import apply_rbo
from repro.core.flexbuild import flexbuild
from repro.serving.session import FlexSession, VersionBus
from repro.serving.writes import WriteSet, split_write_plan, stage_writes
from repro.storage.gart import GARTStore
from repro.storage.generators import (E_BUY, E_KNOWS, V_ITEM, V_PERSON,
                                      snb_store)
from repro.storage.lpg import PropertyGraph


def small_gart(seed=0, n_persons=150, n_items=80, n_posts=20):
    cs = snb_store(n_persons=n_persons, n_items=n_items, n_posts=n_posts,
                   seed=seed)
    return GARTStore.from_csr(cs)


# ===================================================================== #
# Mutation IR: parsing, binding, optimizer opacity                      #
# ===================================================================== #

class TestMutationIR:
    def test_create_parses_bound_endpoints(self):
        p = parse_cypher("MATCH (a:Person {id: $x}), (b:Person {id: $y}) "
                         "CREATE (a)-[:KNOWS {date: $d}]->(b)")
        ins = p.ops[-1]
        assert isinstance(ins, InsertEdge)
        assert (ins.src, ins.dst, ins.edge_label) == ("a", "b", E_KNOWS)
        assert p.param_names() == {"x", "y", "d"}
        assert plan_is_write(p)

    def test_create_self_resolving_endpoints(self):
        p = parse_cypher("CREATE (x {id: $s})-[:BUY]->(y {id: $t})")
        ins = p.ops[0]
        assert ins.src_pred is not None and ins.dst_pred is not None
        bound = p.bind({"s": 1, "t": 2})
        assert bound.param_names() == set()

    def test_create_reversed_arrow(self):
        p = parse_cypher("MATCH (a {id: 1}), (b {id: 2}) "
                         "CREATE (a)<-[:KNOWS]-(b)")
        ins = p.ops[-1]
        assert (ins.src, ins.dst) == ("b", "a")

    def test_create_requires_edge_label(self):
        with pytest.raises(SyntaxError):
            parse_cypher("MATCH (a), (b) CREATE (a)-->(b)")

    def test_create_without_edge_rejected(self):
        with pytest.raises(SyntaxError):
            parse_cypher("CREATE (a {id: 1})")

    def test_bare_unbound_create_endpoint_rejected(self):
        """openCypher would allocate a node for a bare unbound endpoint;
        resolving it against every vertex would fan one CREATE into N
        edges, so it is rejected at parse time."""
        with pytest.raises(SyntaxError, match="unbound"):
            parse_cypher("MATCH (a:Person {id: 1}) CREATE (a)-[:KNOWS]->(b)")

    def test_set_parses_expressions(self):
        p = parse_cypher("MATCH (a:Person) WHERE a.credits > $t "
                         "SET a.credits = a.credits + 10, a.flag = 1")
        assert isinstance(p.ops[-1], SetProp)
        assert isinstance(p.ops[-2], SetProp)
        assert p.param_names() == {"t"}

    def test_gremlin_add_e_and_property(self):
        p = parse_gremlin("g.V().has('id', $v)"
                          ".add_e('KNOWS', $dst, 'date', 7)"
                          ".property('credits', $c)")
        kinds = [type(op).__name__ for op in p.ops]
        assert kinds[-2:] == ["InsertEdge", "SetProp"]
        assert p.param_names() == {"v", "dst", "c"}

    def test_rbo_cbo_keep_mutations_as_opaque_tail(self):
        from repro.core.ir.cbo import apply_cbo

        raw = parse_cypher("MATCH (a:Person)-[:KNOWS]->(b:Person) "
                           "WHERE b.credits > 100 SET b.hot = 1")
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        plan = apply_cbo(apply_rbo(raw), Catalog.build(pg))
        assert isinstance(plan.ops[-1], SetProp)
        assert plan.ops[-1] == raw.ops[-1]      # untouched by both passes
        assert plan_is_write(plan)

    def test_write_plans_never_route_to_read_engines(self):
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        cat = Catalog.build(pg)
        p = apply_rbo(parse_cypher(
            "MATCH (a:Person {id: $x}) SET a.credits = $c"))
        assert not is_point_lookup(p, cat)       # despite the indexed anchor
        assert not should_use_fragment_path(p, cat, 0.0)

    def test_interpreter_rejects_mutations(self):
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        p = parse_cypher("MATCH (a {id: 1}) SET a.credits = 0")
        with pytest.raises(NotImplementedError, match="write route"):
            execute_plan(p, pg)

    def test_return_after_mutation_rejected(self):
        p = parse_cypher("MATCH (a {id: 1}) SET a.credits = 1 "
                         "RETURN a.credits AS c")
        with pytest.raises(NotImplementedError, match="write plans end"):
            split_write_plan(p)

    def test_edge_props_in_match_filter(self):
        """The _EDGE regex gained a props group; in MATCH it filters."""
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        r_all = execute_plan(apply_rbo(parse_cypher(
            "MATCH (a:Person)-[e:REVIEW]->(i:Item) "
            "WITH COUNT(a) AS n RETURN n AS n")), pg)
        r_5 = execute_plan(apply_rbo(parse_cypher(
            "MATCH (a:Person)-[e:REVIEW {rating: 5}]->(i:Item) "
            "WITH COUNT(a) AS n RETURN n AS n")), pg)
        assert 0 < r_5["n"][0] < r_all["n"][0]

    def test_clause_keywords_not_split_inside_refs(self):
        """`$set` params / `a.set` property accesses are not clauses."""
        p = parse_cypher("MATCH (a:Person) WHERE a.credits > $set "
                         "RETURN a AS a")
        assert p.param_names() == {"set"}


# ===================================================================== #
# Staging semantics                                                     #
# ===================================================================== #

class TestStaging:
    def test_stage_is_pure_and_apply_commits(self):
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        plan = apply_rbo(parse_cypher(
            "MATCH (a {id: $x}), (b {id: $y}) CREATE (a)-[:KNOWS]->(b)"))
        v_before = store.write_version
        ws = stage_writes(plan, pg, {"x": 3, "y": 4})
        assert store.write_version == v_before          # staging is pure
        assert ws.n_edges == 1 and ws.n_set == 0
        v = ws.apply(store)
        assert v == v_before + 1
        assert store.n_edges == pg.grin.n_edges + 1

    def test_set_from_with_aggregate(self):
        """SET consuming a WITH aggregate: materialize per-item buyer
        counts as a stored property."""
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        plan = apply_rbo(parse_cypher(
            "MATCH (p:Person)-[:BUY]->(i:Item) WITH i, COUNT(p) AS k "
            "SET i.buyers = k"))
        ws = stage_writes(plan, pg)
        ws.apply(store)
        snap = store.snapshot()
        got = snap.vertex_prop("buyers")
        # numpy oracle: BUY in-degree per item over person sources
        vlab = snap.vertex_labels()
        indptr, indices = pg.grin.adjacency()
        src = np.repeat(np.arange(pg.n_vertices), np.diff(indptr))
        m = (pg.elabels == E_BUY) & (vlab[src] == V_PERSON)
        want = np.bincount(indices[m], minlength=pg.n_vertices)
        items_hit = np.unique(indices[m][vlab[indices[m]] == V_ITEM])
        np.testing.assert_array_equal(got[items_hit], want[items_hit])

    def test_broadcast_mismatch_raises(self):
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        plan = parse_cypher(          # 150 persons x 80 items: no broadcast
            "CREATE (x:Person)-[:KNOWS]->(y:Item)")
        with pytest.raises(ValueError, match="must match"):
            stage_writes(plan, pg)

    def test_empty_endpoint_raises(self):
        store = small_gart()
        pg = PropertyGraph(store.snapshot())
        plan = parse_cypher("CREATE (x {id: 99999})-[:KNOWS]->(y {id: 1})")
        with pytest.raises(ValueError, match="matched no vertices"):
            stage_writes(plan, pg)

    def test_staging_error_rejects_without_discarding_tenants(self):
        """A data-dependent write error (endpoint matches nothing) is an
        admission rejection: the flush raises, nothing commits, and the
        other tenants' requests are requeued intact."""
        s = FlexSession(small_gart())
        sv = s.interactive()
        v_before = s.store.write_version
        sv.submit(Q_CRED, {"x": 3})
        sv.submit("CREATE (x {id: 99999})-[:KNOWS]->(y {id: 1})")
        sv.submit(Q_CRED, {"x": 4})
        with pytest.raises(ValueError, match="matched no vertices"):
            sv.flush()
        assert s.store.write_version == v_before     # nothing committed
        assert len(sv._queue) == 2                   # valid reads requeued
        rs, _ = sv.flush()
        assert [r.engine for r in rs] == ["hiactor", "hiactor"]

    def test_future_version_pin_rejected(self):
        s = FlexSession(small_gart())
        with pytest.raises(ValueError, match="future"):
            s.at((s.version or 0) + 10)

    def test_unbound_set_alias_rejected_at_parse(self):
        """A typo'd SET alias must not silently update every vertex."""
        with pytest.raises(SyntaxError, match="not bound"):
            parse_cypher("MATCH (a:Person {id: $x}) SET b.credits = 0")

    def test_noop_write_commits_nothing(self):
        """A write whose MATCH matches zero rows: no version bump, no
        rebind epoch, no history growth — just a zero-count response."""
        s = FlexSession(small_gart())
        epochs = []
        s.bus.subscribe("probe", epochs.append)
        v = s.version
        hist_len = len(s.store._vprop_hist["credits"])
        r = s.execute("MATCH (a:Person {id: 999999}) SET a.credits = 1")
        assert r["updated"][0] == 0 and r["version"][0] == v
        assert s.version == v and epochs == []
        assert len(s.store._vprop_hist["credits"]) == hist_len

    def test_session_execute_with_prequeued_requests(self):
        """execute() drains the shared queue; it must return THIS
        request's response (last submitted), not the first queued one."""
        s = FlexSession(small_gart())
        s.interactive().submit(Q_CRED, {"x": 1})
        got = s.execute("MATCH (a:Person {id: $x}) RETURN a.region AS r",
                        {"x": 2})
        assert set(got) == {"r"}


# ===================================================================== #
# Differential: write-then-read vs numpy oracle, F in {1, 2, 4}         #
# ===================================================================== #

class NumpyOracle:
    """Mirror of the mutable graph: edge lists + property columns, with
    the 2-hop aggregate computed by dense matrix products."""

    def __init__(self, store: GARTStore):
        snap = store.snapshot()
        indptr, indices = snap.adjacency()
        self.n = snap.n_vertices
        self.src = list(np.repeat(np.arange(self.n), np.diff(indptr)))
        self.dst = list(np.asarray(indices))
        self.elab = list(np.asarray(snap.edge_labels()))
        self.vlab = np.asarray(snap.vertex_labels())
        self.credits = snap.vertex_prop("credits").astype(np.int64).copy()

    def add_edge(self, s, d, lab):
        self.src.append(int(s))
        self.dst.append(int(d))
        self.elab.append(int(lab))

    def set_credits(self, vid, value):
        self.credits[int(vid)] = int(value)

    def _label_matrix(self, lab):
        a = np.zeros((self.n, self.n), np.int64)
        src, dst = np.array(self.src), np.array(self.dst)
        m = np.array(self.elab) == lab
        np.add.at(a, (src[m], dst[m]), 1)
        return a

    def two_hop_counts(self):
        """MATCH (a:Person)-[:KNOWS]->(b:Person)-[:BUY]->(c:Item)
        WITH c, COUNT(a) AS k RETURN k AS k — bag of per-item counts."""
        P = self.vlab == V_PERSON
        I = self.vlab == V_ITEM
        a1 = self._label_matrix(E_KNOWS) * np.outer(P, P)
        a2 = self._label_matrix(E_BUY) * np.outer(P, I)
        k = (a1 @ a2).sum(axis=0)
        return {"k": np.sort(k[I & (k > 0)])}

    def credits_of(self, vid):
        return {"c": np.array([self.credits[int(vid)]])}


Q_HOP = ("MATCH (a:Person)-[:KNOWS]->(b:Person)-[:BUY]->(c:Item) "
         "WITH c, COUNT(a) AS k RETURN k AS k")
Q_CRED = "MATCH (a:Person {id: $x}) RETURN a.credits AS c"
W_CREATE = ("MATCH (a:Person {id: $x}), (b:Person {id: $y}) "
            "CREATE (a)-[:KNOWS]->(b)")
W_SET = "MATCH (a:Person {id: $x}) SET a.credits = $c"


@pytest.mark.parametrize("n_frags", [1, 2, 4])
class TestWriteReadDifferential:
    def _session(self, n_frags):
        store = small_gart(seed=2)
        s = FlexSession(store, n_frags=n_frags, fragment_min_cost=0.0)
        return s, NumpyOracle(store)

    def test_across_flush_visibility(self, n_frags):
        s, oracle = self._session(n_frags)
        sv = s.interactive()
        sv.submit(Q_HOP)
        rs, _ = sv.flush()
        assert rs[0].engine == "fragment"        # the route under test
        assert_results_bag_equal(oracle.two_hop_counts(),
                                 {"k": np.sort(rs[0].result["k"])})
        for step in range(3):                    # write flush, read flush
            x, y = 10 + step, 50 + 3 * step
            sv.submit(W_CREATE, {"x": x, "y": y})
            sv.submit(W_SET, {"x": x, "c": 7000 + step})
            sv.flush()
            oracle.add_edge(x, y, E_KNOWS)
            oracle.set_credits(x, 7000 + step)
            sv.submit(Q_HOP)
            sv.submit(Q_CRED, {"x": x})
            rs, _ = sv.flush()
            # a stale slab would reproduce the pre-write counts here
            assert rs[0].engine == "fragment"
            assert_results_bag_equal(oracle.two_hop_counts(),
                                     {"k": np.sort(rs[0].result["k"])})
            assert_results_bag_equal(oracle.credits_of(x), rs[1].result)

    def test_within_flush_reads_pin_admission_snapshot(self, n_frags):
        s, oracle = self._session(n_frags)
        sv = s.interactive()
        pre = oracle.two_hop_counts()
        # read, write, read in ONE flush: both reads see the admission
        # snapshot (the write commits at flush end — DESIGN.md §11)
        sv.submit(Q_HOP)
        sv.submit(W_CREATE, {"x": 11, "y": 52})
        sv.submit(Q_HOP)
        rs, stats = sv.flush()
        assert stats.route_counts == {"fragment": 2, "write": 1}
        assert_results_bag_equal(pre, {"k": np.sort(rs[0].result["k"])})
        assert_results_bag_equal(pre, {"k": np.sort(rs[2].result["k"])})
        oracle.add_edge(11, 52, E_KNOWS)
        sv.submit(Q_HOP)
        rs, _ = sv.flush()
        assert_results_bag_equal(oracle.two_hop_counts(),
                                 {"k": np.sort(rs[0].result["k"])})

    def test_write_prefixes_stage_against_pinned_snapshot(self, n_frags):
        """Two increments of one cell in ONE flush both read the pinned
        value (last-writer-wins); across flushes they accumulate."""
        s, oracle = self._session(n_frags)
        base = int(oracle.credits[5])
        inc = "MATCH (a:Person {id: $x}) SET a.credits = a.credits + 10"
        sv = s.interactive()
        sv.submit(inc, {"x": 5})
        sv.submit(inc, {"x": 5})
        sv.flush()
        assert s.execute(Q_CRED, {"x": 5})["c"][0] == base + 10
        s.execute(inc, {"x": 5})
        assert s.execute(Q_CRED, {"x": 5})["c"][0] == base + 20


# ===================================================================== #
# Invalidation bus, time travel, cache behaviour                        #
# ===================================================================== #

class TestInvalidation:
    def test_routes_and_plans_survive_policy(self):
        s = FlexSession(small_gart(), fragment_min_cost=0.0)
        sv = s.interactive()
        sv.submit(Q_HOP)
        rs, _ = sv.flush()
        assert rs[0].cached is False
        s.execute(W_SET, {"x": 1, "c": 1})
        sv.submit(Q_HOP)
        rs, _ = sv.flush()
        # plan cache survives the epoch (plans are data-independent);
        # the route memo was dropped and recomputed on the new engines
        assert rs[0].cached is True
        assert rs[0].engine == "fragment"

    def test_hiactor_point_lookup_reindexes_after_write(self):
        s = FlexSession(small_gart())
        sv = s.interactive()
        sv.submit(Q_CRED, {"x": 9})
        rs, _ = sv.flush()
        assert rs[0].engine == "hiactor"
        before = rs[0].result["c"][0]
        s.execute(W_SET, {"x": 9, "c": int(before) + 500})
        sv.submit(Q_CRED, {"x": 9})
        rs, _ = sv.flush()
        # a stale sorted index would still answer with the old value
        assert rs[0].engine == "hiactor"
        assert rs[0].result["c"][0] == before + 500

    def test_bus_notifies_subscribers_once_per_commit(self):
        s = FlexSession(small_gart())
        seen = []
        s.bus.subscribe("probe", seen.append)
        s.execute(W_SET, {"x": 0, "c": 1})
        s.execute(W_SET, {"x": 1, "c": 2})
        assert len(seen) == 2 and seen == sorted(seen)
        s.bus.unsubscribe("probe")
        s.execute(W_SET, {"x": 2, "c": 3})
        assert len(seen) == 2

    def test_raising_subscriber_does_not_lose_committed_flush(self):
        """By publish time the writes ARE committed: a raising user
        subscriber must not discard the flush's responses (a retry would
        double-apply the write). It is recorded and warned instead."""
        s = FlexSession(small_gart())
        s.bus.subscribe("bad", lambda v: 1 / 0)
        v = s.version
        with pytest.warns(RuntimeWarning, match="subscriber raised"):
            r = s.execute(W_SET, {"x": 1, "c": 42})
        assert r["updated"][0] == 1              # response survived
        assert s.version == v + 1                # commit stands
        assert isinstance(s.last_publish_error, ZeroDivisionError)
        s.bus.unsubscribe("bad")
        s.execute(W_SET, {"x": 2, "c": 43})
        assert s.last_publish_error is None      # cleared on a clean epoch

    def test_versionbus_error_isolation(self):
        bus = VersionBus()
        calls = []
        bus.subscribe("bad", lambda v: 1 / 0)
        bus.subscribe("good", calls.append)
        with pytest.raises(ZeroDivisionError):
            bus.publish(1)
        assert calls == [1]                     # later subscriber still ran

    def test_learning_sampler_rebinds_on_commit(self):
        store = small_gart()
        rng = np.random.default_rng(0)
        store._vprops["feat"] = rng.standard_normal(
            (store.n_vertices, 8)).astype(np.float32)
        store._vprop_hist["feat"] = [(0, store._vprops["feat"])]
        s = FlexSession(store)
        samp0 = s.learning().sampler()
        assert s.learning().sampler() is samp0   # cached within a version
        s.execute("MATCH (a {id: 0}), (b {id: 1}) CREATE (a)-[:KNOWS]->(b)")
        samp1 = s.learning().sampler()
        assert samp1 is not samp0
        assert samp1.grin.n_edges == samp0.grin.n_edges + 1

    def test_at_is_read_only_and_lru_bounded(self):
        s = FlexSession(small_gart(), max_pinned=2)
        versions = []
        for k in range(3):
            versions.append(s.version)
            s.execute(W_SET, {"x": k, "c": 100 + k})
        pinned = [s.at(v) for v in versions]
        assert len(s._pinned) == 2               # LRU evicted the first
        assert s.at(versions[-1]) is pinned[-1]
        with pytest.raises(PermissionError):
            pinned[0].execute(W_SET, {"x": 0, "c": 0})

    def test_time_travel_credits(self):
        s = FlexSession(small_gart())
        v0 = s.version
        base = s.execute(Q_CRED, {"x": 4})["c"][0]
        s.execute(W_SET, {"x": 4, "c": int(base) + 999})
        assert s.execute(Q_CRED, {"x": 4})["c"][0] == base + 999
        assert s.at(v0).execute(Q_CRED, {"x": 4})["c"][0] == base


# ===================================================================== #
# flexbuild integration + acceptance                                    #
# ===================================================================== #

class TestSessionSurface:
    def test_flexbuild_serve_returns_session(self):
        store = small_gart()
        s = flexbuild(store, ["cypher", "gaia", "hiactor", "grape"],
                      serve=True)
        assert isinstance(s, FlexSession) and s.mutable
        dep = flexbuild(store, ["cypher", "gaia"])
        s2 = dep.session()
        assert isinstance(s2, FlexSession)
        with pytest.raises(TypeError):
            flexbuild(store, ["cypher"], batch_size=8)   # needs serve=True

    def test_flexbuild_serve_refuses_mesh(self):
        """The session path takes no mesh: asking for one must fail
        loudly, never serve single-device in silence."""
        import jax

        mesh = jax.make_mesh((1,), ("data",))
        with pytest.raises(TypeError, match="mesh"):
            flexbuild(small_gart(), ["cypher", "grape"], mesh=mesh,
                      serve=True)
        dep = flexbuild(small_gart(), ["grape"], mesh=mesh)
        assert dep.engine("grape").mesh is mesh

    def test_gremlin_write_through_session(self):
        s = FlexSession(small_gart())
        r = s.execute("g.V().has('id', $v).add_e('KNOWS', $d)"
                      ".property('credits', $c)",
                      {"v": 2, "d": 3, "c": 123}, language="gremlin")
        assert r["inserted"][0] == 1 and r["updated"][0] == 1
        got = s.execute("g.V().has('id', 2).values('credits')",
                        language="gremlin")
        assert got["credits"][0] == 123

    def test_acceptance_four_verbs_one_store(self):
        """One session drives all four verbs over a single GARTStore:
        CREATE/SET through interactive(), then CALL algo.pagerank and a
        gnn.infer over the post-write snapshot differ from pre-write
        exactly as the oracle predicts, while a reader pinned at the
        pre-write version reproduces its originals bit-for-bit."""
        from repro.engines.grape import GrapeEngine
        from repro.engines.grape.algorithms import pagerank

        store = small_gart(seed=5, n_persons=100, n_items=50, n_posts=10)
        rng = np.random.default_rng(1)
        store._vprops["feat"] = rng.standard_normal(
            (store.n_vertices, 8)).astype(np.float32)
        store._vprops["label"] = rng.integers(
            0, 3, store.n_vertices).astype(np.int32)
        for name in ("feat", "label"):
            store._vprop_hist[name] = [(0, store._vprops[name])]
        s = FlexSession(store, label_prop="label")
        v0 = s.version

        # --- learning: train briefly, register the model for serving
        trainer = s.learning().trainer(hidden=8, n_classes=3,
                                       fanouts=[3, 2], batch_size=32)
        for step in range(2):
            trainer.train_on(trainer.sample(step))
        s.learning().register_inference(trainer)

        # --- pre-write: analytics + inference through the query surface
        pr0 = s.execute("CALL algo.pagerank(0.85) YIELD v, rank "
                        "RETURN rank AS r")["r"]
        inf0 = s.execute("CALL gnn.infer('default') YIELD v, score "
                         "RETURN score AS sc")["sc"]

        # --- interactive writes: new edges + a property update
        sv = s.interactive()
        for k in range(12):
            sv.submit(W_CREATE, {"x": k, "y": (k * 7 + 13) % 100})
        sv.submit(W_SET, {"x": 0, "c": 9999})
        sv.flush()
        assert s.version != v0

        # --- post-write: both differ, exactly as the offline oracles say
        pr1 = s.execute("CALL algo.pagerank(0.85) YIELD v, rank "
                        "RETURN rank AS r")["r"]
        inf1 = s.execute("CALL gnn.infer('default') YIELD v, score "
                         "RETURN score AS sc")["sc"]
        assert not np.array_equal(pr0, pr1)
        assert not np.array_equal(inf0, inf1)
        # served pagerank warm-starts from the v0 fixpoint (DESIGN.md §15):
        # same fixpoint to the documented contraction bound tol/(1-damping),
        # not bit-identical to this cold-started oracle
        want_pr1 = np.asarray(pagerank(
            GrapeEngine(store.snapshot()), damping=0.85))[:store.n_vertices]
        assert float(np.abs(pr1 - want_pr1).sum()) <= 1e-6 / (1 - 0.85)
        want_inf1 = trainer.infer_scores(store=store.snapshot())
        np.testing.assert_array_equal(inf1, want_inf1)

        # --- pinned reader at v0: bit-for-bit reproduction (memo path)
        old = s.at(v0)
        np.testing.assert_array_equal(
            old.execute("CALL algo.pagerank(0.85) YIELD v, rank "
                        "RETURN rank AS r")["r"], pr0)
        np.testing.assert_array_equal(
            old.execute("CALL gnn.infer('default') YIELD v, score "
                        "RETURN score AS sc")["sc"], inf0)
        # ... and with every memo dropped: recomputed from the v0
        # snapshot's data, still bit-for-bit (no stale state anywhere)
        s.procedures.clear()
        np.testing.assert_array_equal(
            old.execute("CALL algo.pagerank(0.85) YIELD v, rank "
                        "RETURN rank AS r")["r"], pr0)
        np.testing.assert_array_equal(
            old.execute("CALL gnn.infer('default') YIELD v, score "
                        "RETURN score AS sc")["sc"], inf0)


# ===================================================================== #
# Incremental rebind vs full rebuild over randomized write sequences    #
# (DESIGN.md §15) — hypothesis-driven when available, seeded otherwise  #
# ===================================================================== #

try:
    from hypothesis import given as _given, settings as _settings
    from hypothesis import strategies as _st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False


def _incremental_vs_rebuild(ops):
    """Drive one session through an arbitrary write sequence; after every
    flush the incrementally-advanced service must agree with a cold
    service rebuilt over the same store, and a reader pinned before any
    write must keep reproducing its original answer bit-for-bit."""
    store = small_gart(seed=2)
    s = FlexSession(store, n_frags=2, fragment_min_cost=0.0)
    oracle = NumpyOracle(store)
    sv = s.interactive()
    v0 = s.version
    sv.submit(Q_HOP)
    rs, _ = sv.flush()
    pinned_k = np.sort(rs[0].result["k"]).copy()
    for i in range(0, len(ops), 3):
        for kind, a, b in ops[i:i + 3]:
            if kind == 0:
                sv.submit(W_CREATE, {"x": a % 150, "y": b % 150})
                oracle.add_edge(a % 150, b % 150, E_KNOWS)
            else:
                sv.submit(W_SET, {"x": a % 150, "c": b})
                oracle.set_credits(a % 150, b)
        sv.flush()
        sv.submit(Q_HOP)
        rs, _ = sv.flush()
        got = {"k": np.sort(rs[0].result["k"])}
        assert_results_bag_equal(oracle.two_hop_counts(), got)
        # cold full-rebuild service over the same store: identical bags
        cold = FlexSession(store, n_frags=2,
                          fragment_min_cost=0.0).interactive()
        cold.submit(Q_HOP)
        rc, _ = cold.flush()
        assert_results_bag_equal({"k": np.sort(rc[0].result["k"])}, got)
    # pinned reader at v0: unchanged by every advance since
    old = s.at(v0)
    np.testing.assert_array_equal(
        np.sort(old.execute(Q_HOP)["k"]), pinned_k)


if _HAVE_HYPOTHESIS:
    class TestIncrementalRebindOracle:
        @_settings(max_examples=10, deadline=None)
        @_given(_st.lists(_st.tuples(_st.integers(0, 1),
                                     _st.integers(0, 10 ** 6),
                                     _st.integers(0, 10 ** 6)),
                          min_size=1, max_size=12))
        def test_randomized_write_sequences(self, ops):
            _incremental_vs_rebuild(ops)
else:
    class TestIncrementalRebindOracle:
        @pytest.mark.parametrize("seed", [0, 1, 2])
        def test_randomized_write_sequences(self, seed):
            rng = np.random.default_rng(seed + 40)
            m = int(rng.integers(1, 12))
            ops = list(zip(rng.integers(0, 2, m).tolist(),
                           rng.integers(0, 10 ** 6, m).tolist(),
                           rng.integers(0, 10 ** 6, m).tolist()))
            _incremental_vs_rebuild(ops)
