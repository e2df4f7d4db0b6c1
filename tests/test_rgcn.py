"""Relational GCN training on a typed store (DESIGN.md §10): the
``rgcn`` brick, the typed draw against a numpy oracle, the fused step
against the plain reference (``learning/rgcn_ref.py``), the sparse
update's untouched rows, and the untyped GraphSAGE program left as it
was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flexbuild import flexbuild
from repro.engines.sample import FragmentSampleExecutor, relation_codes
from repro.kernels.ref import sampler_ref
from repro.kernels.sampler import csr_to_sample_ell, layer_uniforms
from repro.learning import rgcn_ref
from repro.learning.sampler import GraphSampler
from repro.learning.trainer import RGCNTrainer, SageTrainer
from repro.storage.csr import CSRStore

# node types: paper 0, author 1, institution 2, field 3; the relations as
# ogbn-mag's seven: (source type, target type, edges, forward, reverse)
COUNTS = (800, 900, 50, 250)
EDGES = ((0, 0, 3000, 0, 0), (1, 0, 3000, 1, 2), (1, 2, 700, 3, 4),
         (0, 3, 3000, 5, 6))
D, C, FANOUTS, BATCH, LR = 16, 5, (4, 3), 32, 0.1
EMBED = (1, 2, 3)


def typed_store(seed=0):
    """About 2k vertices of four types, 19k arcs under seven relations,
    each arc in the row of its target; papers are ids 0 .. 799."""
    rng = np.random.default_rng(seed)
    offs = np.cumsum((0,) + COUNTS)
    n = int(offs[-1])
    types = np.repeat(np.arange(4), COUNTS).astype(np.int32)
    tgt, src, rel = [], [], []
    for s, t, m, fwd, rev in EDGES:
        a = offs[s] + rng.integers(0, COUNTS[s], m)
        b = offs[t] + rng.integers(0, COUNTS[t], m)
        tgt += [b, a]
        src += [a, b]
        rel += [np.full(m, fwd), np.full(m, rev)]
    tgt, src, rel = map(np.concatenate, (tgt, src, rel))
    feat = rng.standard_normal((n, D)).astype(np.float32)
    label = np.where(types == 0, rng.integers(0, C, n), 0).astype(np.int32)
    return CSRStore(n, tgt, src, vertex_props={"feat": feat, "label": label},
                    vertex_labels=types, edge_labels=rel)


@pytest.fixture(scope="module")
def store():
    return typed_store()


def trainer_of(store, seed=3):
    dep = flexbuild(store, ["vineyard", "graphlearn", "rgcn"],
                    feature_prop="feat", label_prop="label")
    return RGCNTrainer(dep.engine("graphlearn"), hidden=8, n_classes=C,
                       fanouts=FANOUTS, seed_type=0, embed_types=EMBED,
                       batch_size=BATCH, lr=LR, seed=seed)


# ------------------------------------------------------------ typed draws
def oracle_walk(store, seeds, key, fanouts):
    """(draws, relations, node types) of a layered walk by the numpy
    oracle over two slabs of the store's CSR: neighbours and relations;
    a vertex's type read from the vertex labels."""
    indptr, indices = store.adjacency()
    ell, deg = csr_to_sample_ell(indptr, indices)
    ell_rel, _ = csr_to_sample_ell(indptr, store.edge_labels())
    vt = np.append(store.vertex_labels(), -1)      # -1: padding, past n
    fr = np.asarray(seeds, np.int64)
    at = lambda ids: vt[np.where((ids >= 0) & (ids < len(vt)), ids, -1)]
    layers, rels, types = [], [], [at(fr)]
    for l, k in enumerate(fanouts):
        u = np.asarray(layer_uniforms(key, l, len(fr), k))
        layers.append(sampler_ref(ell, deg, fr, u))
        rels.append(sampler_ref(ell_rel, deg, fr, u))
        fr = layers[-1].reshape(-1)
        types.append(at(fr))
    return layers, rels, types


@pytest.mark.parametrize("n_frags", (1, 2, 4))
@pytest.mark.parametrize("exchange", ("stacked", "psum"))
def test_typed_draw_matches_oracle(store, n_frags, exchange):
    ex = FragmentSampleExecutor(store, n_frags=n_frags, label_prop="label",
                                exchange=exchange, typed=True)
    key = jax.random.PRNGKey(11)
    # papers, authors, an institution, a field, padding and an id past n
    seeds = np.array(list(range(0, 1990, 41)) + [-1, store.n_vertices + 5],
                     np.int32)
    layers, _, _, rels, types = ex.sample(seeds, key, FANOUTS)
    want_l, want_r, want_t = oracle_walk(store, seeds, key, FANOUTS)
    for got, ref in zip(layers, want_l):
        np.testing.assert_array_equal(np.asarray(got), ref)
    for got, ref in zip(rels, want_r):
        np.testing.assert_array_equal(np.asarray(got), ref)
    fronts = [seeds] + [l.reshape(-1) for l in want_l]
    for got, ref, fr in zip(types, want_t, fronts):
        real = (fr >= 0) & (fr < store.n_vertices)
        np.testing.assert_array_equal(np.asarray(got)[real], ref[real])


def test_typed_executor_does_not_advance():
    # its slab holds relation codes, which the incremental patch would
    # overwrite with bare neighbour ids: callers rebuild
    from repro.storage.gart import GARTStore

    g = GARTStore.from_csr(typed_store())
    ex = FragmentSampleExecutor(g.snapshot(), label_prop="label", typed=True)
    v0 = g.write_version
    g.add_edges(np.array([0]), np.array([1]), label=0)   # a paper cites one
    assert ex.advance(g.snapshot(), g.commit_delta(v0)) is None


def test_relation_codes_refuse_mixed_sources():
    # relation 0 sends from a type-0 and from a type-1 vertex
    with pytest.raises(ValueError, match="more than one node type"):
        relation_codes(np.array([0, 1]), np.array([0, 0]), np.array([0, 1]))


def test_rgcn_brick_builds_typed_sampler(store):
    typed = flexbuild(store, ["vineyard", "graphlearn", "rgcn"],
                      feature_prop="feat", label_prop="label")
    plain = flexbuild(store, ["vineyard", "graphlearn", "sage"],
                      feature_prop="feat", label_prop="label")
    assert typed.engine("graphlearn").device_executor().n_relations == 7
    assert plain.engine("graphlearn").device_executor().n_relations == 0
    with pytest.raises(ValueError, match="typed sampler"):
        RGCNTrainer(plain.engine("graphlearn"), hidden=8, n_classes=C,
                    fanouts=FANOUTS, seed_type=0, embed_types=EMBED)
    # authors are ids 800 .. 1699, but one contiguous range is required
    shuffled = typed_store()
    shuffled._vlabels = shuffled._vlabels.copy()
    shuffled._vlabels[[0, 900]] = shuffled._vlabels[[900, 0]]
    with pytest.raises(ValueError):
        trainer_of(shuffled)


# ---------------------------------------------------- step vs reference
@pytest.fixture(scope="module")
def one_step(store):
    """A trainer's state before and after one fused step, the batch the
    step drew, and the reference's step from the same state."""
    tr = trainer_of(store)
    ex = tr._executor
    step = 5
    key = jax.random.fold_in(jax.random.PRNGKey(3), np.uint32(step))
    seeds = np.random.default_rng(step).integers(
        *tr._seed_range, BATCH).astype(np.int32)
    layers, feats, labels, rels, types = ex.sample(seeds, key, FANOUTS)
    table0 = np.asarray(ex.feats).copy()
    params0 = jax.tree_util.tree_map(np.asarray, tr.params)
    loss = tr.train_step_device(step)
    fronts = [jnp.asarray(seeds)] + [l.reshape(-1) for l in layers]
    vt = np.append(store.vertex_labels(), -1)
    learned = jnp.asarray(np.isin(vt, EMBED))
    batch = (fronts, rels, types, labels, FANOUTS)
    ref_loss, ref_params, ref_table = rgcn_ref.sgd_step(
        params0, jnp.asarray(table0), learned, *batch, LR)
    return dict(tr=tr, loss=loss, table0=table0, params0=params0,
                table1=np.asarray(ex.feats),
                params1=jax.tree_util.tree_map(np.asarray, tr.params),
                feats=feats, batch=batch, ref_loss=float(ref_loss),
                ref_params=ref_params, ref_table=np.asarray(ref_table))


def test_logits_and_loss_match_reference(one_step):
    s = one_step
    fronts, rels, types, labels, fanouts = s["batch"]
    got = s["tr"].model.logits(s["params0"], s["feats"], rels, types)
    with jax.default_matmul_precision("highest"):
        want = rgcn_ref.logits(s["params0"], jnp.asarray(s["table0"]),
                               fronts, rels, types, fanouts)
    # float32 on the CPU: the one-hot contraction sums in another order
    # than the reference's relation-by-relation means, a few ulps of
    # logits of size about 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert s["loss"] == pytest.approx(s["ref_loss"], rel=1e-6)


def test_weight_gradients_match_reference(one_step):
    s = one_step
    # the gradient SGD applied, (w0 - w1) / lr, against the reference's;
    # a weight of size about 0.3 keeps float32's 7 digits through one
    # step of lr 0.1, so the recovered gradient carries about 1e-6 of it
    for got0, got1, ref1 in zip(*(jax.tree_util.tree_leaves(t) for t in (
            s["params0"], s["params1"], s["ref_params"]))):
        g_prog = (got0 - got1) / LR
        g_ref = (got0 - np.asarray(ref1)) / LR
        np.testing.assert_allclose(g_prog, g_ref, rtol=1e-3, atol=1e-5)


def test_embedding_rows_match_reference(one_step):
    s = one_step
    moved = np.any(s["table1"] != s["table0"], axis=1)
    assert moved.sum() > 0
    # duplicate draws of one row sum their gradients in the scatter's
    # order, the reference's dense autodiff in its own: a few ulps of the
    # rows, which are of size about 1
    np.testing.assert_allclose(s["table1"], s["ref_table"], rtol=0,
                               atol=2e-6)
    assert s["tr"].embed_rows == int(moved.sum())


def test_untouched_rows_are_bit_identical(one_step, store):
    s = one_step
    fronts = np.concatenate([np.asarray(f) for f in s["batch"][0]])
    drawn = np.zeros(len(s["table0"]), bool)
    drawn[fronts[fronts >= 0]] = True
    papers = np.append(store.vertex_labels() == 0, True)   # and padding
    same = np.all(s["table1"] == s["table0"], axis=1)
    assert np.all(same[~drawn]) and np.all(same[papers])
    assert not np.all(same[drawn & ~papers])


def test_duplicate_draws_sum_before_the_row_moves(store):
    """A row drawn 1,000 times, each draw's step a quarter of a unit in
    the last place of the row's value: the steps add up first and the row
    moves by 250 units, as the dense gradient moves it; added one by one,
    each would round away. A paper among the draws stays as it was."""
    tr = trainer_of(store)
    n = store.n_vertices
    table = jnp.ones((n + 1, D), jnp.float32)
    author, paper = 800, 3
    step = np.spacing(np.float32(1)) / 4          # a quarter ulp of 1
    fr = jnp.array([author] * 1000 + [paper], jnp.int32)
    types = jnp.array([1] * 1000 + [0], jnp.int32)
    grads = jnp.full((1001, D), -step / LR, jnp.float32)
    new, count = jax.jit(tr._embed_update)(table, [fr], [types], [grads])
    new = np.asarray(new)
    np.testing.assert_allclose(new[author] - 1, 250 * np.spacing(
        np.float32(1)), rtol=1e-2)
    assert np.all(new[paper] == 1) and int(count) == 1
    moved = np.flatnonzero(np.any(new != 1, axis=1))
    assert moved.tolist() == [author]


def test_loss_falls(store):
    tr = trainer_of(store, seed=7)
    losses = [tr.train_step_device(i) for i in range(30)]
    assert np.mean(losses[-5:]) < losses[0]


# ------------------------------------------------- the untyped program
def _primitives(trainer, *args):
    jaxpr = jax.make_jaxpr(trainer._device_step_fn)(*args)
    out = []

    def walk(j):
        for eqn in j.eqns:
            out.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return out


def test_untyped_sage_step_has_no_relation_or_type_op(store):
    """GraphSAGE over the same store, untyped: its step lowers as before,
    with no relation decode (``rem``) and no type gather: three gathers a
    hop (the degree, the row start, the neighbour), one a frontier's
    rows, one of the labels and the loss's gold logit."""
    s = GraphSampler(store, label_prop="label", backend="device")
    tr = SageTrainer(s, hidden=8, n_classes=C, fanouts=FANOUTS,
                     batch_size=BATCH, backend="device")
    assert {"types", "rel_types"}.isdisjoint(tr._executor._tables)
    seeds = np.zeros(BATCH, np.int32)
    prims = _primitives(tr, tr.params, tr._executor._tables, np.uint32(0),
                        seeds)
    assert "rem" not in prims
    assert prims.count("gather") == 3 * len(FANOUTS) + len(FANOUTS) + 1 + 2
    typed = trainer_of(store)
    ex = typed._executor
    prims = _primitives(typed, typed.params, ex.feats,
                        {**ex._tables, "feats": None}, np.uint32(0), seeds)
    assert "rem" in prims
