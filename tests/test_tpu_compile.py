"""The main path's device programs compile for a TPU v5e chip at the
widths ``chip_smoke.py`` runs, without a chip attached: the compiler is
given a described ``v5e:2x2`` topology and shapes only, so it refuses
here what it would refuse on the chip (unsupported ops, layouts, memory).
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module fixture — never while a module
is imported — so every test worker collects the same tests and only the
worker that runs this file loads the TPU compiler.
"""

import copy
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The served deployment of chip_smoke.py: snb_store(500k persons, 250k
# items, 125k posts) — 875k vertices; KNOWS ≈ 16 and BUY = 12 directed
# edges per person. The sharded one: Graph500 RMAT scale 21, factor 16.
N_PERSONS, N_ITEMS, N_POSTS = 500_000, 250_000, 125_000
N_SNB = N_PERSONS + N_ITEMS + N_POSTS
E_KNOWS, E_BUY = 16 * N_PERSONS, 12 * N_PERSONS
E_SNB = 33 * N_PERSONS
RMAT_N, RMAT_E = 1 << 21, 16 << 21
BATCH = 64
FEAT_DIM, HIDDEN, N_CLASSES, FANOUTS, SAGE_BATCH = 100, 256, 47, (15, 10), 1024

TWO_HOP_TOPK = ("MATCH (a:Person)-[:KNOWS]->(b:Person)-[:BUY]->(c:Item) "
                "WHERE a.credits < $t WITH c, COUNT(*) AS k "
                "RETURN c AS c, k AS k ORDER BY k DESC LIMIT 10")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    """Arrays → shape/dtype stand-ins placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


_HLO_OP = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([a-z][a-z0-9-]*)\((.*)")


def _sorts_and_scatters_of(hlo: str, n: int) -> list:
    """The ``sort`` and ``scatter`` ops of compiled HLO text that give or
    take an array of ``n`` elements (``[n]``, ``[1,n]`` or ``[n,1]``).
    The text names an op's operands without their shapes, so each is
    looked up where it is defined."""
    shape_of, ops = {}, []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m:
            shape_of[m.group(1)] = m.group(2)
            ops.append(m)
    sized = re.compile(rf"\[(1,)?{n}(,1)?\]")
    found = []
    for m in ops:
        if m.group(3) not in ("sort", "scatter"):
            continue
        operands = re.findall(r"%[\w.\-]+", m.group(4).split(")")[0])
        shapes = [m.group(2)] + [shape_of.get(o, "") for o in operands]
        if any(sized.search(s) for s in shapes):
            found.append(m.group(0).strip()[:200])
    return found


def _snb_shaped_store():
    """The served graph's vertex count, labels and properties with a
    handful of edges: what the fragment executor derives from the graph
    (vertex masks, program structure) at the real width, while the edge
    arrays' real sizes are given to the compiler as shapes."""
    from repro.storage.csr import CSRStore
    from repro.storage.generators import (E_BUY as L_BUY, E_KNOWS as L_KNOWS,
                                          V_ITEM, V_PERSON, V_POST)

    vlab = np.concatenate([np.full(N_PERSONS, V_PERSON, np.int32),
                           np.full(N_ITEMS, V_ITEM, np.int32),
                           np.full(N_POSTS, V_POST, np.int32)])
    rng = np.random.default_rng(0)
    src = np.array([0, 1, 2, 3], np.int64)
    dst = np.array([1, 2, N_PERSONS, N_PERSONS + 1], np.int64)
    elab = np.array([L_KNOWS, L_KNOWS, L_BUY, L_BUY], np.int32)
    vprops = {"id": np.arange(N_SNB, dtype=np.int64),
              "credits": rng.integers(0, 1000, N_SNB).astype(np.int32)}
    return CSRStore(N_SNB, src, dst, vertex_props=vprops,
                    vertex_labels=vlab, edge_labels=elab)


class TestServedPathCompiles:
    def test_fragment_two_hop_device_tail(self, one_chip):
        """The fused prefix+tail program of a 2-hop group-count top-k at
        B=64 over 875k vertices (DESIGN.md §14)."""
        from repro.engines.frontier import FragmentFrontierExecutor
        from repro.engines.gaia import GaiaEngine
        from repro.storage.generators import E_KNOWS as L_KNOWS

        gaia = GaiaEngine(_snb_shaped_store())
        ex = FragmentFrontierExecutor(gaia.pg)
        program = ex.program_for(gaia.compile(TWO_HOP_TOPK))
        tail = ex._device_tail(program)
        assert program is not None and tail is not None
        params = [{"t": 10}] * BATCH
        src = ex._stage_mask(program.source_alias, program.source_label,
                             program.source_pred, params)
        x0 = jnp.broadcast_to(src, (BATCH, N_SNB)).astype(jnp.float32)
        masks = tuple(ex._stage_mask(h.vertex_alias, h.vertex_label,
                                     h.vertex_pred, params)
                      for h in program.hops)
        pvals = ex._tail_pvals(tail, params)
        props = {p: ex._tail_prop(p) for p in tail.prop_refs}
        # each hop's (src, row, w) [F, Ep] at the real edge count, with
        # the executor's capacity slack
        hops = []
        for h in program.hops:
            e = E_KNOWS if h.edge_label == L_KNOWS else E_BUY
            ep = -(-max(e + e // 4, e + 128) // 128) * 128
            hops.append((jax.ShapeDtypeStruct((1, ep), jnp.int32),
                         jax.ShapeDtypeStruct((1, ep), jnp.int32),
                         jax.ShapeDtypeStruct((1, ep), jnp.float32)))
        hops = _sds(tuple(hops), one_chip)
        runner = ex._tail_runner(program, tail)
        _compile(runner, *_sds((x0, masks, pvals), one_chip), hops,
                 _sds(props, one_chip))

    def test_grape_pagerank_fixpoint(self, one_chip):
        """The whole pagerank while-loop over RMAT scale 21 on one chip,
        its arcs grouped by target at set-up: no sort and no scatter of
        the arcs in the superstep, whose two halves carry their scopes."""
        from repro.engines.grape import GrapeEngine, algorithms as alg
        from repro.engines.grape.engine import FragmentArrays

        def pagerank_program(frags):
            eng = GrapeEngine.__new__(GrapeEngine)
            eng.mesh, eng.n_frags, eng.frags = None, 1, frags
            eng.use_kernels, eng._sharded = False, {}
            return alg.pagerank(eng)

        def sds(n, dt):
            return jax.ShapeDtypeStruct((1, n), dt, sharding=one_chip)

        frags = FragmentArrays(
            indices=sds(RMAT_E, jnp.int32), e_src=sds(RMAT_E, jnp.int32),
            e_mask=sds(RMAT_E, jnp.bool_), weights=None,
            run_last=sds(RMAT_N, jnp.int32), run_has=sds(RMAT_N, jnp.bool_),
            owned_start=jax.ShapeDtypeStruct((1,), jnp.int32,
                                             sharding=one_chip),
            out_degree=jax.ShapeDtypeStruct((RMAT_N,), jnp.int32,
                                            sharding=one_chip),
            n_vertices=RMAT_N, v_per_frag=RMAT_N)
        hlo = _compile(pagerank_program, frags)
        assert _sorts_and_scatters_of(hlo, RMAT_E) == []
        assert "grape.gather" in hlo and "grape.combine" in hlo

    def test_fused_sage_train_step(self, one_chip):
        """sample → gather → SGD as one program (DESIGN.md §10) at feature
        width 100 over the served graph's vertex and edge counts."""
        _compile(*_sage_step(one_chip, one_chip, N_SNB, E_SNB, FANOUTS))

    @pytest.mark.parametrize("row_major", [False, True])
    def test_sage_step_reads_resident_table_layout(self, one_chip,
                                                   row_major):
        """Over ogbn-products' vertex and arc counts the chip's default
        layout for the feature table is column-major, and the step then
        copies the whole table into the row-major layout its gathers read;
        given the table in that layout (``engines/sample.py``
        ``resident_table``), it copies nothing."""
        import re

        from jax.experimental.layout import Format, Layout

        n, e = 2_449_029, 123_718_280
        device, = one_chip.device_set
        default = Layout.from_pjrt_layout(device.client.get_default_layout(
            np.dtype(np.float32), (n + 1, FEAT_DIM), device))
        assert default.major_to_minor == (1, 0)
        table = (Format(Layout((0, 1)), one_chip) if row_major
                 else one_chip)
        hlo = _compile(*_sage_step(one_chip, table, n, e, FANOUTS))
        shape = re.escape(f"f32[{n + 1},{FEAT_DIM}]")
        entry = re.search(r"entry_computation_layout=\{\((.*?)\)->",
                          hlo).group(1)
        assert re.findall(shape + r"\{(\d),(\d)", entry) == [
            ("1", "0") if row_major else ("0", "1")]
        assert bool(re.findall(shape + r"\S* copy\(", hlo)) != row_major

    def test_rgcn_train_step_at_ogbn_mag_size(self, one_chip):
        """The fused R-GCN step (typed draws, per-relation means, the
        sparse update of the donated input table) over ogbn-mag's 1.94M
        vertices and 42.2M arcs at its published widths: the table is
        updated in place, in the row-major layout its gathers read (the
        chip's default at width 128), without a copy, and the program
        fits a chip with room to spare."""
        import re

        from jax.experimental.layout import Layout

        n, e = 1_939_743, 42_222_014
        device, = one_chip.device_set
        default = Layout.from_pjrt_layout(device.client.get_default_layout(
            np.dtype(np.float32), (n + 1, 128), device))
        assert default.major_to_minor == (0, 1)
        fn, params, table, tables, step, seeds = _rgcn_step(one_chip, n, e)
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, table, tables, step, seeds).compile()
        hlo = compiled.as_text()
        table_param = len(jax.tree_util.tree_leaves(params))
        assert re.search(r"input_output_alias=\{[^\n]*\(" + str(table_param)
                         + r", \{\}", hlo)
        assert not re.findall(re.escape(f"f32[{n + 1},128]") + r"\S* copy\(",
                              hlo)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4e9


def _rgcn_step(one_chip, n, e):
    """The fused R-GCN step of a trainer built over a small typed store,
    and its argument shapes over ``n`` vertices and ``e`` arcs: hidden
    64, 349 classes, fanouts 25/20, batch 1024, 7 relations, 4 types."""
    import copy

    from repro.core.flexbuild import flexbuild
    from repro.learning.trainer import RGCNTrainer
    from repro.storage.csr import CSRStore

    # papers, authors, an institution, a field; each relation of ogbn-mag
    types = np.array([0, 0, 1, 1, 2, 3], np.int32)
    tgt = np.array([0, 1, 0, 2, 4, 3, 0, 5])
    src = np.array([1, 0, 2, 0, 2, 4, 5, 0])
    rel = np.array([0, 0, 1, 2, 3, 4, 6, 5])
    rng = np.random.default_rng(0)
    small = CSRStore(6, tgt, src, vertex_props={
        "feat": rng.standard_normal((6, 128)).astype(np.float32),
        "label": np.zeros(6, np.int32)}, vertex_labels=types,
        edge_labels=rel)
    dep = flexbuild(small, ["vineyard", "graphlearn", "rgcn"],
                    feature_prop="feat", label_prop="label")
    tr = RGCNTrainer(dep.engine("graphlearn"), hidden=64, n_classes=349,
                     fanouts=(25, 20), seed_type=0, embed_types=(1, 2, 3),
                     batch_size=1024)
    tr = copy.copy(tr)
    tr._executor = copy.copy(tr._executor)
    tr._executor.n_vertices = n
    S = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    tables = {"ell": None, "starts": None, "feats": None, "deg": S((n,)),
              "labels": S((n + 1,)), "csr_starts": S((n,)),
              "csr_indices": S((e + 1,)), "types": S((n + 1,)),
              "rel_types": S((7,))}
    return (tr._device_step_fn, _sds(tr.params, one_chip),
            S((n + 1, 128), jnp.float32), tables, S((), jnp.uint32),
            S((1024,)))


def _sage_step(one_chip, feats_sharding, n, e, fanouts):
    """The fused GraphSAGE step and its argument shapes over ``n`` vertices
    and ``e`` arcs, its feature table placed by ``feats_sharding`` (a
    sharding or a format)."""
    from repro.learning.sampler import GraphSampler
    from repro.learning.trainer import SageTrainer
    from repro.storage.generators import snb_store

    small = snb_store(n_persons=64, n_items=32, n_posts=16, seed=0)
    rng = np.random.default_rng(0)
    small._vprops["feat"] = rng.standard_normal(
        (small.n_vertices, FEAT_DIM)).astype(np.float32)
    small._vprops["label"] = rng.integers(
        0, N_CLASSES, small.n_vertices).astype(np.int32)
    tr = SageTrainer(GraphSampler(small, label_prop="label",
                                  backend="device"),
                     hidden=HIDDEN, n_classes=N_CLASSES,
                     fanouts=fanouts, batch_size=SAGE_BATCH,
                     backend="device")
    # the same program over the real vertex count
    tr = copy.copy(tr)
    tr._executor = copy.copy(tr._executor)
    tr._executor.n_vertices = n
    tables = {"ell": None, "starts": None,
              "deg": jax.ShapeDtypeStruct((n,), jnp.int32),
              "feats": jax.ShapeDtypeStruct((n + 1, FEAT_DIM), jnp.float32),
              "labels": jax.ShapeDtypeStruct((n + 1,), jnp.int32),
              "csr_starts": jax.ShapeDtypeStruct((n,), jnp.int32),
              "csr_indices": jax.ShapeDtypeStruct((e + 1,), jnp.int32)}
    tables = _sds(tables, one_chip)
    tables["feats"] = jax.ShapeDtypeStruct((n + 1, FEAT_DIM), jnp.float32,
                                           sharding=feats_sharding)
    return (tr._device_step_fn, _sds(tr.params, one_chip), tables,
            jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip),
            jax.ShapeDtypeStruct((SAGE_BATCH,), jnp.int32,
                                 sharding=one_chip))


class TestKernelsCompile:
    def test_tail_reduce_grid(self, one_chip):
        from repro.kernels.reduce import tail_reduce_grid

        x = jax.ShapeDtypeStruct((BATCH, 1 << 20), jnp.float32,
                                 sharding=one_chip)
        v = jax.ShapeDtypeStruct((2, 1 << 20), jnp.float32,
                                 sharding=one_chip)
        hlo = _compile(lambda a, b: tail_reduce_grid(a, b), x, v)
        assert "tpu_custom_call" in hlo

    def test_flash_attention(self, one_chip):
        from repro.kernels.flash_attention import flash_attention_bhsd

        q = jax.ShapeDtypeStruct((4, 256, 128), jnp.bfloat16,
                                 sharding=one_chip)
        hlo = _compile(lambda a, b, c: flash_attention_bhsd(
            a, b, c, block_q=128, block_kv=128), q, q, q)
        assert "tpu_custom_call" in hlo
