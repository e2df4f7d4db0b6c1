"""Sharding rules + multi-device behaviour (subprocess with 8 fake devices:
train-step sharded == single-device reference; GRAPE shard_map == vmap;
elastic checkpoint restore onto a different mesh; pipeline-parallel loss ==
non-pipelined loss)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.distributed.sharding import MeshRules, logical_to_spec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class TestLogicalSpecs:
    def _mesh(self):
        # an abstract mesh stand-in: only .axis_names and .shape are used
        class M:
            axis_names = ("data", "model")
            shape = {"data": 4, "model": 2}
        return M()

    def test_divisibility_stripping(self):
        rules = MeshRules(tensor=("model",), fsdp=("data",))
        spec = logical_to_spec(("kv_heads", None), (3, 16), self._mesh(), rules)
        assert spec == jax.sharding.PartitionSpec()  # 3 % 2 != 0 → replicate

    def test_duplicate_axis_stripping(self):
        rules = MeshRules(expert=("model",), tensor=("model",))
        spec = logical_to_spec(("expert", "expert_ff"), (4, 8),
                               self._mesh(), rules)
        # model used by expert dim; expert_ff must not reuse it
        assert spec[0] == "model"
        assert len(spec) == 1 or spec[1] is None

    def test_multi_axis_batch(self):
        class M:
            axis_names = ("pod", "data", "model")
            shape = {"pod": 2, "data": 4, "model": 2}
        rules = MeshRules(batch=("pod", "data"))
        spec = logical_to_spec(("act_batch", "act_seq"), (16, 128), M(), rules)
        assert spec[0] == ("pod", "data")

    def test_missing_axis_restriction(self):
        rules = MeshRules(batch=("pod", "data")).restrict_to(("data", "model"))
        assert rules.batch == ("data",)


_SUBPROCESS_TEMPLATE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys
    sys.path.insert(0, {src!r})
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    {body}
""")


def run_sub(body: str, n_devices: int = 8) -> dict:
    code = _SUBPROCESS_TEMPLATE.format(src=os.path.abspath(SRC),
                                       body=textwrap.dedent(body),
                                       n=n_devices)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestMultiDevice:
    def test_sharded_train_step_matches_single(self):
        r = run_sub("""
            from repro.configs import get_smoke
            from repro.configs.base import ShapeConfig, TrainConfig
            from repro.models import build_model
            from repro.train.train_step import (init_train_state,
                make_train_step, train_state_axes)
            from repro.distributed.sharding import (MeshRules,
                shardings_for_tree, use_rules)

            m = build_model(get_smoke('qwen2-72b'))
            tcfg = TrainConfig(microbatches=2)
            shape = ShapeConfig('t', seq_len=32, global_batch=8, kind='train')
            state = init_train_state(m, tcfg, jax.random.PRNGKey(0))
            batch = m.dummy_inputs(shape)['batch']
            step = make_train_step(m, tcfg,
                                   batch_axes=m.input_axes(shape)['batch'])

            # single-device reference
            ref_state, ref_metrics = jax.jit(step)(state, batch)
            ref_loss = float(ref_metrics['loss'])

            mesh = jax.make_mesh((4, 2), ('data', 'model'))
            rules = MeshRules(batch=('data',), fsdp=('data',),
                              tensor=('model',), expert=('model',))
            saxes = train_state_axes(m)
            ssh = shardings_for_tree(state, saxes, mesh, rules)
            bsh = shardings_for_tree(batch, m.input_axes(shape)['batch'],
                                     mesh, rules)
            state_s = jax.device_put(state, ssh)
            batch_s = jax.device_put(batch, bsh)
            with mesh, use_rules(rules):
                out_state, metrics = jax.jit(
                    step, in_shardings=(ssh, bsh),
                    out_shardings=(ssh, None))(state_s, batch_s)
            loss = float(metrics['loss'])
            p1 = jax.tree_util.tree_leaves(ref_state['params'])[0]
            p2 = jax.tree_util.tree_leaves(out_state['params'])[0]
            diff = float(jnp.max(jnp.abs(p1.astype(jnp.float32)
                                          - p2.astype(jnp.float32))))
            print(json.dumps({'ref_loss': ref_loss, 'loss': loss,
                              'param_diff': diff}))
        """)
        assert abs(r["ref_loss"] - r["loss"]) < 1e-2
        assert r["param_diff"] < 1e-2

    def test_grape_shard_map_matches_local(self):
        r = run_sub("""
            from repro.storage.generators import rmat_store
            from repro.engines.grape import GrapeEngine, algorithms as alg

            g = rmat_store(scale=7, edge_factor=6, seed=2)
            mesh = jax.make_mesh((8,), ('data',))
            e_local = GrapeEngine(g, n_frags=8)
            e_dist = GrapeEngine(g, n_frags=8, mesh=mesh)
            p1 = np.asarray(alg.pagerank(e_local, max_steps=15))
            p2 = np.asarray(alg.pagerank(e_dist, max_steps=15))
            print(json.dumps({'diff': float(np.abs(p1 - p2).max())}))
        """)
        assert r["diff"] < 1e-5

    @pytest.mark.parametrize("combiner", ["sum", "min", "max"])
    def test_grape_superstep_replicated_on_four_devices(self, combiner):
        """The sharded superstep hands back the collective's result as one
        unsharded [N] vector, equal to the single-device stacked form."""
        r = run_sub(f"""
            from repro.storage.generators import rmat_store
            from repro.engines.grape import GrapeEngine

            g = rmat_store(scale=8, edge_factor=4, seed=3)
            mesh = jax.make_mesh((4,), ('data',))
            e_local = GrapeEngine(g, n_frags=4)
            e_dist = GrapeEngine(g, n_frags=4, mesh=mesh)
            vals = jnp.asarray(np.random.default_rng(0).random(
                g.n_vertices).astype(np.float32))
            want = e_local.superstep(e_local.owned_view(vals),
                                     {combiner!r}, use_weights=True)
            got = e_dist.superstep(e_dist.owned_view(vals),
                                   {combiner!r}, use_weights=True)
            print(json.dumps({{
                'shape': list(got.shape), 'n': g.n_vertices,
                'replicated': bool(got.sharding.is_fully_replicated),
                'equal': bool(np.allclose(np.asarray(got),
                                          np.asarray(want), rtol=1e-6))}}))
        """, n_devices=4)
        assert r["shape"] == [r["n"]]
        assert r["replicated"]
        assert r["equal"]

    def test_elastic_checkpoint_reshard(self):
        r = run_sub("""
            import tempfile
            from repro.configs import get_smoke
            from repro.configs.base import TrainConfig
            from repro.models import build_model
            from repro.train import checkpoint as ckpt
            from repro.train.train_step import init_train_state, train_state_axes
            from repro.distributed.sharding import MeshRules, shardings_for_tree

            m = build_model(get_smoke('mistral-nemo-12b'))
            state = init_train_state(m, TrainConfig(), jax.random.PRNGKey(1))
            saxes = train_state_axes(m)
            mesh8 = jax.make_mesh((4, 2), ('data', 'model'))
            rules = MeshRules()
            sh8 = shardings_for_tree(state, saxes, mesh8, rules)
            state8 = jax.device_put(state, sh8)
            d = tempfile.mkdtemp()
            ckpt.save(d, 7, state8)

            # restore onto a DIFFERENT mesh (2x2 — elastic downscale)
            mesh4 = jax.make_mesh((2, 2), ('data', 'model'))
            sh4 = shardings_for_tree(state, saxes, mesh4, rules)
            restored = ckpt.restore(d, 7, state, shardings=sh4)
            a = jax.tree_util.tree_leaves(state)[0]
            b = jax.tree_util.tree_leaves(restored)[0]
            diff = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
            ndev = len({d for l in jax.tree_util.tree_leaves(restored)
                        for d in l.devices()})
            print(json.dumps({'diff': diff, 'ndev': ndev}))
        """)
        assert r["diff"] == 0.0
        assert r["ndev"] == 4

    def test_pipeline_parallel_matches_reference(self):
        r = run_sub("""
            from repro.distributed.pipeline_parallel import gpipe_loss

            n_stages, n_micro, mb, d = 4, 8, 2, 16
            mesh = jax.make_mesh((4,), ('pod',))
            key = jax.random.PRNGKey(0)
            w = jax.random.normal(key, (n_stages, d, d), jnp.float32) * 0.2
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (n_micro, mb, d), jnp.float32)
            y = jax.random.normal(jax.random.PRNGKey(2),
                                  (n_micro, mb, d), jnp.float32)

            def stage_fn(wi, h):
                return jnp.tanh(h @ wi)

            def loss_fn(h, yy):
                return jnp.mean((h - yy) ** 2)

            pl = float(gpipe_loss(stage_fn, loss_fn, w, x, y,
                                  mesh=mesh, axis='pod'))

            # non-pipelined reference
            def fwd(h):
                for s in range(n_stages):
                    h = stage_fn(w[s], h)
                return h
            ref = float(np.mean([loss_fn(fwd(x[i]), y[i])
                                 for i in range(n_micro)]))
            # gradient check too
            g = jax.grad(lambda ww: gpipe_loss(stage_fn, loss_fn, ww, x, y,
                                               mesh=mesh, axis='pod'))(w)

            def ref_loss(ww):
                tot = 0.0
                for i in range(n_micro):
                    h = x[i]
                    for s in range(n_stages):
                        h = stage_fn(ww[s], h)
                    tot = tot + loss_fn(h, y[i])
                return tot / n_micro
            gr = jax.grad(ref_loss)(w)
            gdiff = float(jnp.max(jnp.abs(g - gr)))
            print(json.dumps({'pl': pl, 'ref': ref, 'gdiff': gdiff}))
        """)
        assert abs(r["pl"] - r["ref"]) < 1e-5
        assert r["gdiff"] < 1e-4
