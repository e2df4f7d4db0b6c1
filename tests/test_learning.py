"""Learning stack: sampler, decoupled pipeline, GraphSAGE/NCN training."""

import time

import jax
import numpy as np
import pytest

from repro.learning.gnn import NCN
from repro.learning.pipeline import DecoupledPipeline, run_pipelined, run_serial
from repro.learning.sampler import GraphSampler
from repro.learning.trainer import SageTrainer
from repro.storage.csr import CSRStore
from repro.storage.generators import rmat_store


@pytest.fixture(scope="module")
def featured_graph():
    g = rmat_store(scale=9, edge_factor=8, seed=4)
    n = g.n_vertices
    rng = np.random.default_rng(0)
    # learnable labels: a linear function of features
    feats = rng.standard_normal((n, 16)).astype(np.float32)
    w = rng.standard_normal((16,))
    labels = (feats @ w > 0).astype(np.int32)
    g._vprops["feat"] = feats
    g._vprops["label"] = labels
    return g


class TestSampler:
    def test_shapes(self, featured_graph):
        s = GraphSampler(featured_graph, label_prop="label")
        b = s.sample_batch(np.arange(32), [5, 3])
        assert b.layers[0].shape == (32, 5)
        assert b.layers[1].shape == (160, 3)
        assert b.features[0].shape == (32, 16)
        assert b.features[2].shape == (480, 16)

    def test_sampled_are_neighbors(self, featured_graph):
        s = GraphSampler(featured_graph, label_prop="label")
        indptr, indices = featured_graph.adjacency()
        b = s.sample_batch(np.arange(64), [4])
        for i in range(64):
            nbrs = set(indices[indptr[i]:indptr[i + 1]].tolist())
            for x in b.layers[0][i]:
                if x >= 0:
                    assert int(x) in nbrs

    def test_ncn_common_neighbors(self, featured_graph):
        s = GraphSampler(featured_graph, label_prop="label")
        indptr, indices = featured_graph.adjacency()
        edges = np.array([[0, 1], [2, 3]])
        out = s.sample_ncn(edges, [3])
        for i, (u, v) in enumerate(edges):
            nu = set(indices[indptr[u]:indptr[u + 1]].tolist())
            nv = set(indices[indptr[v]:indptr[v + 1]].tolist())
            for c in out["common"][i]:
                if c >= 0:
                    assert int(c) in (nu & nv)


class TestPipeline:
    def test_produces_all_batches(self):
        pipe = DecoupledPipeline(lambda step: step, n_workers=2, depth=4)
        got = sorted(pipe.get()[0] for _ in range(16))
        pipe.close()
        assert len(set(got)) == 16       # no dup/dropped steps

    def test_pipelining_overlaps(self):
        """With slow sampling + slow training, pipelined wall-time must be
        clearly under the serial sum (the Exp-4 mechanism)."""
        def sample(step):
            time.sleep(0.02)
            return step

        def train(batch):
            time.sleep(0.02)

        t_serial = run_serial(sample, train, 20)
        t_pipe = run_pipelined(sample, train, 20, n_workers=2)
        assert t_pipe < t_serial * 0.8


class TestTraining:
    def test_sage_loss_decreases(self, featured_graph):
        # fixed PRNG seed end-to-end (model init + per-step sampling) makes
        # the run reproducible; lr=0.1 for 60 steps converges well past the
        # 30%-drop bar (observed final/first ≈ 0.52), so the threshold stays
        # meaningful without being flaky
        s = GraphSampler(featured_graph, label_prop="label")
        tr = SageTrainer(s, hidden=32, n_classes=2, fanouts=[5, 3],
                         batch_size=128, lr=0.1, seed=0)
        first = tr.train_on(tr.sample(0))
        losses = [tr.train_on(tr.sample(i)) for i in range(1, 60)]
        assert np.mean(losses[-5:]) < first * 0.7

    def test_ncn_scores_finite(self, featured_graph):
        s = GraphSampler(featured_graph, label_prop="label")
        model = NCN(s.feature_dim, hidden=16, fanouts=[4])
        params = model.init(jax.random.PRNGKey(0))
        edges = np.stack([np.arange(8), np.arange(8) + 1], axis=1)
        raw = s.sample_ncn(edges, [4])
        batch = {
            "u_feats": raw["u_batch"].features,
            "u_nbrs": raw["u_batch"].layers,
            "v_feats": raw["v_batch"].features,
            "v_nbrs": raw["v_batch"].layers,
            "cn_feats": raw["cn_batch"].features,
            "cn_nbrs": raw["cn_batch"].layers,
            "common": raw["common"],
        }
        scores = model.score(params, batch)
        assert scores.shape == (8,)
        assert np.isfinite(np.asarray(scores)).all()


class TestPipelineLifecycle:
    """Shutdown/liveness contract (ISSUE 4): close() always joins workers —
    even when they are blocked on a full channel — and the counters satisfy
    produced == consumed + drained afterwards."""

    def test_close_joins_workers_under_full_queue(self):
        pipe = DecoupledPipeline(lambda step: step, n_workers=3, depth=2)
        deadline = time.time() + 5
        while pipe.stats["produced"] < 2 and time.time() < deadline:
            time.sleep(0.01)                 # channel fills; workers block
        assert pipe.close() is True
        assert all(not w.is_alive() for w in pipe._workers)
        s = pipe.stats
        assert s["produced"] == s["consumed"] + s["drained"]

    def test_stats_conserved_under_concurrency(self):
        pipe = DecoupledPipeline(lambda step: step, n_workers=4, depth=8)
        for _ in range(100):
            pipe.get()
        assert pipe.close() is True
        s = pipe.stats
        assert s["consumed"] == 100
        assert s["produced"] == s["consumed"] + s["drained"]

    def test_trainer_starved_regime_terminates(self):
        """Slow sampler, eager trainer: the trainer blocks in get(); close()
        still joins the worker once its in-flight sample returns."""
        def slow_sample(step):
            time.sleep(0.05)
            return step

        pipe = DecoupledPipeline(slow_sample, n_workers=1, depth=4)
        step, _ = pipe.get(timeout=10.0)
        assert step == 0
        assert pipe.close() is True
        assert pipe.stats["trainer_wait_s"] > 0

    def test_sampler_starved_regime_terminates(self):
        """Eager samplers, slow trainer: workers park on the full channel
        and accumulate sampler_wait; close() drains and joins them."""
        pipe = DecoupledPipeline(lambda step: step, n_workers=2, depth=1)
        pipe.get(timeout=10.0)
        time.sleep(0.1)                       # let both workers block on put
        assert pipe.close() is True
        assert pipe.stats["sampler_wait_s"] > 0
        assert pipe.stats["produced"] == (pipe.stats["consumed"]
                                          + pipe.stats["drained"])

    def test_device_prefetch_batches_are_device_resident(self):
        def sample(step):
            return {"x": np.ones(4, np.float32), "step": step}

        pipe = DecoupledPipeline(sample, n_workers=1, depth=2,
                                 prefetch="device")
        try:
            _, batch = pipe.get(timeout=10.0)
            assert isinstance(batch["x"], jax.Array)
            np.testing.assert_array_equal(np.asarray(batch["x"]), np.ones(4))
        finally:
            pipe.close()

    def test_invalid_prefetch_mode_rejected(self):
        with pytest.raises(ValueError):
            DecoupledPipeline(lambda s: s, prefetch="nope")

    def test_run_pipelined_device_prefetch(self):
        seen = []
        t = run_pipelined(lambda s: np.full(2, s, np.float32),
                          lambda b: seen.append(np.asarray(b).sum()),
                          steps=6, n_workers=2, prefetch="device")
        assert t > 0 and len(seen) == 6


class TestDeviceTraining:
    def test_device_loss_decreases(self, featured_graph):
        s = GraphSampler(featured_graph, label_prop="label",
                         backend="device")
        tr = SageTrainer(s, hidden=32, n_classes=2, fanouts=[5, 3],
                         batch_size=128, lr=0.1, seed=0, backend="device")
        _, losses = tr.train(40)
        assert np.mean(losses[-5:]) < losses[0] * 0.8

    def test_device_backend_requires_labels(self, featured_graph):
        s = GraphSampler(featured_graph, backend="device")
        with pytest.raises(ValueError):
            SageTrainer(s, hidden=8, n_classes=2, fanouts=[3],
                        backend="device")

    def test_invalid_backends_rejected(self, featured_graph):
        with pytest.raises(ValueError):
            GraphSampler(featured_graph, backend="gpu")
        s = GraphSampler(featured_graph, label_prop="label")
        with pytest.raises(ValueError):
            SageTrainer(s, hidden=8, n_classes=2, fanouts=[3],
                        backend="quantum")

    def test_device_batch_shares_host_contract(self, featured_graph):
        """backend="device" sample_batch returns the host SampledBatch
        layout: same shapes/dtypes, identical labels, -1 padding."""
        sd = GraphSampler(featured_graph, label_prop="label",
                          backend="device")
        sh = GraphSampler(featured_graph, label_prop="label")
        bd = sd.sample_batch(np.arange(8), [3, 2])
        bh = sh.sample_batch(np.arange(8), [3, 2])
        assert [l.shape for l in bd.layers] == [l.shape for l in bh.layers]
        assert [f.shape for f in bd.features] == \
            [f.shape for f in bh.features]
        assert all(l.dtype == np.int64 for l in bd.layers)
        assert all(f.dtype == np.float32 for f in bd.features)
        np.testing.assert_array_equal(bd.labels, bh.labels)
        indptr, indices = featured_graph.adjacency()
        for i in range(8):
            nbrs = set(indices[indptr[i]:indptr[i + 1]].tolist())
            assert set(int(x) for x in bd.layers[0][i] if x >= 0) <= nbrs

    def test_device_trainer_reproducible(self, featured_graph):
        mk = lambda: SageTrainer(
            GraphSampler(featured_graph, label_prop="label",
                         backend="device"),
            hidden=16, n_classes=2, fanouts=[4, 2], batch_size=32,
            lr=0.05, seed=9, backend="device")
        a, b = mk(), mk()
        la = [a.train_step_device(i) for i in range(3)]
        lb = [b.train_step_device(i) for i in range(3)]
        assert la == lb


class TestStepTracing:
    """The fused step names its parts (DESIGN.md §10): device scopes in
    the compiled program's op_name metadata, host spans in a profile."""

    @pytest.fixture(scope="class")
    def trainer(self, featured_graph):
        return SageTrainer(
            GraphSampler(featured_graph, label_prop="label",
                         backend="device"),
            hidden=16, n_classes=2, fanouts=[4, 3, 2], batch_size=32,
            backend="device")

    def test_device_step_carries_scopes(self, trainer):
        import re

        text = trainer._device_step.lower(
            trainer.params, trainer._executor._tables, np.uint32(0),
            np.zeros(32, np.int32)).compile().as_text()
        scopes = {part for name in re.findall(r'op_name="([^"]*)"', text)
                  for part in name.split("/")}
        assert {"sample.hop0", "sample.hop1", "sample.hop2",
                "gather.features", "gather.labels", "model.fwd_bwd",
                "model.update"} <= scopes

    def test_step_host_spans_nest_in_order(self, trainer, tmp_path):
        import glob

        from jax.profiler import ProfileData

        trainer.train_step_device(0)                # compile outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            for step in (1, 2):
                trainer.train_step_device(step)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        # by start, an enclosing span before the spans it holds
        spans = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for plane in ProfileData.from_file(path).planes
                        for ln in plane.lines for ev in ln.events
                        if ev.name.startswith("flex.")),
                       key=lambda sp: (sp[0], -sp[1]))
        inner = ["flex.learning.seeds", "flex.learning.dispatch",
                 "flex.learning.loss_wait"]
        assert [n for _, _, n in spans] == (["flex.learning.step"]
                                            + inner) * 2
        for i in (0, 4):
            s0, e0, _ = spans[i]
            kids = spans[i + 1:i + 4]
            assert all(s0 <= s <= e <= e0 for s, e, _ in kids)
            assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:]))


def _column_major(table):
    """A TPU's default layout for a narrow table, built by hand: CPU
    places ``f32[n, D]`` row-major, a TPU column-major."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    device, = table.devices()
    return jax.device_put(table, Format(Layout(tuple(reversed(range(
        table.ndim)))), SingleDeviceSharding(device)))


def _with_feats(ex, feats):
    ex.feats = feats
    ex._tables = ex._make_tables()
    return ex


class TestResidentTableLayout:
    """The feature table is placed once in the row-major layout its gathers
    read (``engines/sample.py`` ``resident_table``; DESIGN.md §10), so the
    fused step takes it as it is and never relays it out."""

    @staticmethod
    def _trainer(graph, feats_layout=None):
        tr = SageTrainer(GraphSampler(graph, label_prop="label",
                                      backend="device"),
                         hidden=16, n_classes=2, fanouts=[4, 3, 2],
                         batch_size=32, lr=0.05, seed=3, backend="device")
        if feats_layout is not None:
            _with_feats(tr._executor, feats_layout(tr._executor.feats))
        return tr

    @staticmethod
    def _step_text(tr):
        return tr._device_step.lower(
            tr.params, tr._executor._tables, np.uint32(0),
            np.zeros(32, np.int32)).compile().as_text()

    @staticmethod
    def _table_layout_and_copies(tr):
        import re

        text = TestResidentTableLayout._step_text(tr)
        n, d = tr._executor.feats.shape
        shape = re.escape(f"f32[{n},{d}]")
        entry = re.search(r"entry_computation_layout=\{\((.*?)\)->",
                          text).group(1)
        layouts = re.findall(shape + r"(\{[^}]*\})", entry)
        copies = re.findall(shape + r"\S* copy\(", text)
        return layouts, copies

    @pytest.mark.parametrize("exchange", ["stacked", "psum"])
    def test_placement_makes_column_major_row_major(self, featured_graph,
                                                    exchange):
        from repro.engines.sample import (FragmentSampleExecutor,
                                          resident_table)

        ex = FragmentSampleExecutor(featured_graph, n_frags=2,
                                    exchange=exchange)
        cm = _column_major(ex.feats)
        assert cm.format.layout.major_to_minor[-1] == 0
        rm = resident_table(cm)
        assert rm.format.layout.major_to_minor == tuple(range(rm.ndim))
        np.testing.assert_array_equal(np.asarray(rm), np.asarray(ex.feats))

    def test_host_table_keeps_default_row_major_layout(self, featured_graph):
        """On CPU the default is already row-major: the placement leaves the
        table as ``jnp.asarray`` puts it, uncommitted."""
        tr = self._trainer(featured_graph)
        feats = tr._executor.feats
        assert feats.format.layout.major_to_minor == (0, 1)
        assert not feats.committed

    def test_step_reads_row_major_table_without_copy(self, featured_graph):
        from repro.engines.sample import resident_table

        rm = self._trainer(featured_graph,
                           lambda t: resident_table(_column_major(t)))
        assert rm._executor.feats.format.layout.major_to_minor == (0, 1)
        layouts, copies = self._table_layout_and_copies(rm)
        assert layouts == ["{1,0}"] and copies == []
        # the regression the placement removes: a column-major argument
        # is relaid out inside the step
        cm = self._trainer(featured_graph, _column_major)
        layouts, copies = self._table_layout_and_copies(cm)
        assert layouts == ["{0,1}"] and copies

    def test_layouts_give_bit_identical_batches_and_steps(self,
                                                          featured_graph):
        from repro.engines.sample import resident_table

        rm = self._trainer(featured_graph,
                           lambda t: resident_table(_column_major(t)))
        cm = self._trainer(featured_graph, _column_major)
        seeds = np.random.default_rng(5).integers(
            0, featured_graph.n_vertices, 32)
        key = jax.random.PRNGKey(7)
        for a, b in zip(jax.tree_util.tree_leaves(
                rm._executor.sample(seeds, key, (4, 3, 2))),
                jax.tree_util.tree_leaves(
                    cm._executor.sample(seeds, key, (4, 3, 2)))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for step in range(3):
            assert rm.train_step_device(step) == cm.train_step_device(step)
        for a, b in zip(jax.tree_util.tree_leaves(rm.params),
                        jax.tree_util.tree_leaves(cm.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_placement_is_not_written_to_persistent_compile_cache(
            self, tmp_path):
        """JAX 0.9 loads a cached program's outputs in the default layout,
        so the relayout the placement runs must never be written to the
        persistent compile cache: a later process would read it back and
        get a column-major table labelled row-major on a TPU (on CPU the
        default is the target, so only the cache's contents show it)."""
        import json
        import os
        import subprocess
        import sys

        script = """if True:
            import json, os
            import jax, jax.numpy as jnp, numpy as np
            from jax.experimental.layout import Format, Layout
            from jax.sharding import SingleDeviceSharding
            from repro.compile_cache import configure_compile_cache
            from repro.engines.sample import resident_table
            cache = configure_compile_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            x = np.arange(40 * 16, dtype=np.float32).reshape(40, 16)
            cm = jax.jit(lambda a: a, out_shardings=Format(
                Layout((1, 0)), SingleDeviceSharding(jax.devices()[0])))(x)
            before = set(os.listdir(cache))
            rm = resident_table(cm)
            by_put = set(os.listdir(cache)) - before
            i = np.array([3, 0, 39, 7], np.int32)
            got = jax.jit(lambda t, i: jnp.take(t, i, axis=0))(rm, i)
            print(json.dumps({
                "in": cm.format.layout.major_to_minor,
                "out": rm.format.layout.major_to_minor,
                "rows": bool(np.array_equal(np.asarray(got), x[i])),
                "by_put": len(by_put),
                "by_gather": len(set(os.listdir(cache)) - before - by_put)}))
        """
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   PYTHONPATH=src + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert json.loads(out.stdout.splitlines()[-1]) == {
            "in": [1, 0], "out": [0, 1], "rows": True, "by_put": 0,
            "by_gather": 1}

    def test_fresh_and_stepped_params_share_one_program(self,
                                                        featured_graph):
        """The committed table makes the step's outputs committed; the step
        and the ``gnn.infer`` runner still lower to one program for fresh
        (uncommitted) parameters and for the ones a step returns."""
        from repro.engines.sample import resident_table

        tr = self._trainer(featured_graph,
                           lambda t: resident_table(_column_major(t)))
        ex = tr._executor
        fresh = tr.params
        tr.train_step_device(0)
        stepped = tr.params
        assert jax.tree_util.tree_leaves(stepped)[0].committed
        step = lambda p: tr._device_step.lower(
            p, ex._tables, np.uint32(1), np.zeros(32, np.int32)).as_text()
        assert step(fresh) == step(stepped)
        run = tr._infer_runner(ex)
        infer = lambda p: run.lower(
            p, ex._tables, jax.random.PRNGKey(0), np.uint32(0),
            np.zeros(tr.INFER_CHUNK, np.int32)).as_text()
        assert infer(fresh) == infer(stepped)

    def test_advance_carries_table_and_infer_compiles_once(self):
        from repro.engines.sample import resident_table
        from repro.storage.gart import GARTStore

        rng = np.random.default_rng(13)
        n, e = 150, 700
        g = GARTStore.from_csr(CSRStore(
            n, rng.integers(0, n, e), rng.integers(0, n, e),
            vertex_props={"feat": rng.random((n, 8)).astype(np.float32),
                          "label": rng.integers(0, 2, n),
                          "age": rng.integers(0, 90, n)}))
        tr = self._trainer(g.snapshot(),
                           lambda t: resident_table(_column_major(t)))
        ex0 = tr._executor
        before = tr.infer_scores()
        np.testing.assert_array_equal(tr.infer_scores(), before)
        assert tr._infer_runners[id(ex0)][1]._cache_size() == 1
        v0 = g.write_version
        g.set_vertex_prop("age", np.array([3, 9]), np.array([1, 2]))
        ex1 = ex0.advance(g.snapshot(), g.commit_delta(v0))
        assert ex1 is not None and ex1._tables["feats"] is ex0.feats
        assert ex1.feats.format.layout.major_to_minor == (0, 1)
        tr.sampler._device = ex1
        np.testing.assert_array_equal(tr.infer_scores(), before)
        np.testing.assert_array_equal(tr.infer_scores(), before)
        assert tr._infer_runners[id(ex1)][1]._cache_size() == 1


class TestReviewRegressions:
    def test_device_prefetch_descends_into_sampled_batch(self, featured_graph):
        """SampledBatch is a plain dataclass, not a registered pytree:
        prefetch="device" must still land its array fields on device."""
        s = GraphSampler(featured_graph, label_prop="label")
        pipe = DecoupledPipeline(
            lambda step: s.sample_batch(np.arange(8), [3, 2]),
            n_workers=1, depth=2, prefetch="device")
        try:
            _, batch = pipe.get(timeout=10.0)
            assert all(isinstance(l, jax.Array) for l in batch.layers)
            assert all(isinstance(f, jax.Array) for f in batch.features)
            assert isinstance(batch.labels, jax.Array)
        finally:
            pipe.close()

    def test_concurrent_device_sampling_unique_steps(self, featured_graph):
        """Pipeline workers draw through one device sampler: every batch
        must come from a distinct fold_in step (no replayed keys)."""
        s = GraphSampler(featured_graph, label_prop="label",
                         backend="device", seed=0)
        pipe = DecoupledPipeline(
            lambda step: s.sample_batch(np.arange(64), [15]),
            n_workers=4, depth=4)
        try:
            batches = [pipe.get(timeout=30.0)[1] for _ in range(12)]
        finally:
            pipe.close()
        fingerprints = {b.layers[0].tobytes() for b in batches}
        assert len(fingerprints) == 12

    def test_foreign_executor_cache_is_bounded(self, featured_graph):
        s = GraphSampler(featured_graph, label_prop="label",
                         backend="device")
        tr = SageTrainer(s, hidden=8, n_classes=2, fanouts=[3],
                         batch_size=16, backend="device")
        stores = []
        for i in range(tr.max_ext_executors + 3):
            g = rmat_store(scale=5, edge_factor=4, seed=i)
            rng = np.random.default_rng(i)
            g._vprops["feat"] = rng.standard_normal(
                (g.n_vertices, 16)).astype(np.float32)
            stores.append(g)
            tr.infer_scores(store=g)
        assert len(tr._ext_executors) == tr.max_ext_executors
        assert len(tr._infer_runners) <= tr.max_ext_executors + 1

    def test_infer_scores_chunk_grid_fixed(self, featured_graph):
        """Serving equality is unconditional: infer_scores exposes no chunk
        knob that could move the fold_in grid away from the served path."""
        import inspect

        assert "chunk" not in inspect.signature(
            SageTrainer.infer_scores).parameters

    def test_device_prefetch_handles_namedtuples(self):
        """NamedTuple batches must reconstruct field-wise — the generic
        tuple rebuild would pass one generator to the N-field ctor."""
        from typing import NamedTuple

        class Batch(NamedTuple):
            x: np.ndarray
            tag: str

        pipe = DecoupledPipeline(
            lambda step: Batch(np.ones(3, np.float32), "b"),
            n_workers=1, depth=2, prefetch="device")
        try:
            _, batch = pipe.get(timeout=10.0)
            assert isinstance(batch, Batch)
            assert isinstance(batch.x, jax.Array) and batch.tag == "b"
        finally:
            pipe.close()

    def test_failed_sampler_surfaces_promptly(self):
        """A sampler worker that raises must not hang the trainer for the
        full get() timeout — the error propagates with the real cause."""
        def bad_sample(step):
            raise RuntimeError("boom")

        pipe = DecoupledPipeline(bad_sample, n_workers=2, depth=2)
        t0 = time.time()
        with pytest.raises(RuntimeError, match="sampler worker failed"):
            pipe.get(timeout=60.0)
        assert time.time() - t0 < 10        # surfaced early, not at timeout
        assert pipe.close() is True
