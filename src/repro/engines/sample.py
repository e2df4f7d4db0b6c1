"""Device-resident GNN sampling on the partitioned fragment substrate
(DESIGN.md §10).

The learning stack's sampling hot path, rebuilt on the same storage + kernel
layer the query engines use: the adjacency is range-partitioned into F
fragments of owned vertex rows (the ``engines/frontier.py`` fragment model),
each fragment holding the per-vertex pull-ELL *sampling slab* of its owned
rows plus the owned slice of the vertex feature matrix. One layered
GraphSAGE batch — fixed-fanout draws per hop, feature gather per frontier —
executes as ONE jitted device program:

    hop l:   nbrs[m, k] = draw(slab_row(frontier[m]), u_l[m, k])
    gather:  feats[m]   = features[frontier[m]]        (0-rows for PAD)

Fragment execution mirrors the frontier executor's exchange rules
(DESIGN.md §9): under a mesh, each fragment computes draws/features only
for the frontier entries whose vertex it owns and the disjoint
contributions combine with a single ``psum`` across the ``data`` axis
under ``shard_map``; on ONE device the same range partition collapses to
a stacked reshape — fragment f's row r IS global row ``f·v_per + r`` — so
the default single-device path (``exchange="stacked"``) draws and gathers
against the flat stacked tables directly, with no per-fragment mask
arithmetic on the hot path. ``exchange="psum"`` keeps the owned-slice
exchange arithmetic selectable on one device so the differential suite
(``tests/test_sampler_diff.py``) can pin stacked ≡ psum ≡ oracle for
F ∈ {1, 2, 4}. Draws ride the psum exchange as ``nbr + 1`` with 0 for
unowned entries, so the sum minus one recovers the owner's draw and
leaves ``PAD_SENTINEL`` (−1) for invalid seeds and isolated vertices —
the stack-wide padding contract (``storage/partition.py``).

A *typed* executor (``typed=True``, the ``rgcn`` brick) serves relational
models on a store whose vertex labels are node types and whose edge labels
are relations, each arc in the row of its target. The relation rides the
draw: the CSR slab holds ``source · R + relation`` (``relation_codes``),
so one random read yields both, and a neighbour's node type is its
relation's source type — only the seeds' types are gathered.

Randomness is a threaded ``jax.random`` key: hop l draws its uniforms from
``fold_in(key, l)`` over the FULL frontier (replicated across fragments), so
results are bit-identical for any F and either exchange — the property the
differential suite pins against the numpy ``sampler_ref`` oracle.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import config as jax_config
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.kernels.sampler import (SLAB_VMEM_BYTES, csr_to_sample_ell,
                                   layer_uniforms, sample_csr_jnp,
                                   sample_ell, sample_ell_jnp,
                                   sample_ell_width)
from repro.storage.grin import GRINAdapter, LEARNING_REQUIRED, Traits
from repro.storage.partition import PAD_SENTINEL

EXCHANGES = ("stacked", "psum")

# ceiling for the dense [F, v_per, W] psum-exchange slab (per §9's fragment
# model it is O(N·d_max)); beyond this, construction refuses with a pointer
# at the O(E) stacked path rather than OOM-ing mid-__init__
PSUM_SLAB_LIMIT_BYTES = 2 ** 31


def resident_table(table) -> jax.Array:
    """One device's copy of a per-vertex table, row-major: the layout its
    row gathers read (DESIGN.md §10).

    A TPU's default layout for a narrow float table is column-major
    (``f32[n, 100]`` pads 100 to 104 where row-major pads it to 128), and
    a jitted gather over an argument in that layout relays the whole table
    out on every call. A committed array carries its layout into every jit
    that takes it, so the table is placed once in the layout the gathers
    read: one host-to-device transfer, relaid out on the device by a small
    program that keeps only the row-major copy. ``table`` is a host array
    (its layout is the device's default) or a device array (its own); one
    that is already row-major, as on CPU, is left as ``jnp.asarray``
    places it."""
    if isinstance(table, jax.Array):
        device, = table.devices()
        layout = table.format.layout
    else:
        device = jax.devices()[0]
        layout = Layout.from_pjrt_layout(device.client.get_default_layout(
            table.dtype, table.shape, device))
    rows = tuple(range(table.ndim))
    if layout.major_to_minor == rows:
        return jnp.asarray(table)
    # JAX 0.9 drops an executable's output layouts when it loads one from
    # the persistent compilation cache, so a cached relayout would hand
    # back a table in (or labelled with) the default layout. The relayout
    # compiles in milliseconds: it is never written there (a thread-local
    # setting, read when an entry would be written), and its program is
    # named for this function, so no cached program of another caller (a
    # plain ``jax.device_put`` runs the same identity) is ever read for it
    with jax_config.persistent_cache_min_compile_time_secs(math.inf):
        return jax.jit(_relayout_rows, out_shardings=Format(
            Layout(rows), SingleDeviceSharding(device)))(table)


def _relayout_rows(table):
    return table


def relation_codes(indices: np.ndarray, relations: np.ndarray,
                   vertex_types: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, int]:
    """The typed draw's slab entries: ``(codes, source type of each
    relation, R)`` with ``codes = indices · R + relations`` (int32), so a
    draw reads an arc's neighbour and relation in one random access
    (DESIGN.md §10). Each relation must send from one node type, as a
    heterogeneous graph's canonical (source type, relation, target type)
    triples do: that type is the drawn neighbour's, with no gather."""
    relations = np.asarray(relations, np.int64)
    vertex_types = np.asarray(vertex_types, np.int64)
    R = int(relations.max()) + 1 if len(relations) else 1
    T = int(vertex_types.max()) + 1 if len(vertex_types) else 1
    if len(vertex_types) * R >= 2 ** 31:
        raise ValueError(f"{len(vertex_types)} vertices × {R} relations "
                         "overflow the int32 relation codes")
    sent = np.zeros(R * T, bool)
    sent[relations * T + vertex_types[indices]] = True
    sent = sent.reshape(R, T)
    mixed = np.flatnonzero(sent.sum(1) > 1)
    if len(mixed):
        raise ValueError(f"relations {mixed.tolist()} send from more than "
                         "one node type")
    codes = (np.asarray(indices, np.int64) * R + relations).astype(np.int32)
    return codes, sent.argmax(1).astype(np.int32), R


def split_codes(codes: jnp.ndarray, n_relations: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Typed draws → (neighbour ids, relations), ``PAD_SENTINEL`` in both
    where the draw is padding."""
    ok = codes >= 0
    safe = jnp.where(ok, codes, 0)
    return (jnp.where(ok, safe // n_relations, PAD_SENTINEL),
            jnp.where(ok, safe % n_relations, PAD_SENTINEL))


class FragmentSampleExecutor:
    """Layered fixed-fanout sampling + feature gather over F fragments."""

    def __init__(self, store, n_frags: int = 1, mesh=None,
                 feature_prop: str = "feat",
                 label_prop: Optional[str] = None,
                 use_kernels: bool = False,
                 interpret: Optional[bool] = None, pg=None,
                 exchange: str = "stacked", typed: bool = False):
        # ``pg`` shares the query engines' PropertyGraph adjacency caches so
        # learning runs off the same partitioned store as traversal
        if pg is not None:
            store = pg.grin.store
        if pg is not None and not typed:
            indptr, indices, _ = pg.sliced_csr(None, "out")
        else:
            indptr, indices = store.adjacency()
        grin = GRINAdapter(store, LEARNING_REQUIRED | (
            Traits.VERTEX_LABEL | Traits.EDGE_LABEL if typed
            else Traits.NONE))
        # typed: R relations ride the slab entries; 0 for an untyped
        # executor, whose program holds no relation or type op
        self.n_relations = 0
        vtypes = rel_types = None
        if typed:
            vtypes = np.asarray(grin.vertex_labels(), np.int32)
            indices, rel_types, self.n_relations = relation_codes(
                indices, grin.edge_labels(), vtypes)
        self.store = store
        self.feature_prop = feature_prop
        self.label_prop = label_prop
        n = grin.n_vertices
        self.n_vertices = n
        self.mesh = mesh
        if mesh is not None:
            if "data" not in mesh.axis_names:
                raise ValueError(
                    "FragmentSampleExecutor shard_maps fragments over the "
                    f"'data' mesh axis; mesh has {mesh.axis_names}")
            n_frags = int(mesh.shape["data"])
        if exchange not in EXCHANGES:
            raise ValueError(f"unknown exchange {exchange!r}; "
                             f"one of {EXCHANGES}")
        self.exchange = "psum" if mesh is not None else exchange
        self.n_frags = n_frags
        self.v_per = -(-n // n_frags)
        # the Pallas slab path needs stacking-free per-fragment dispatch;
        # under a mesh the hop runs the jnp form inside shard_map (the same
        # rule as FragmentFrontierExecutor)
        self.use_kernels = use_kernels and mesh is None
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret

        F, vp = self.n_frags, self.v_per
        deg = np.diff(indptr).astype(np.int32)
        feats = np.asarray(grin.vertex_prop(feature_prop), np.float32)
        if feats.ndim == 1:
            feats = feats[:, None]
        self.feature_dim = feats.shape[1]
        lab = None
        if label_prop is not None:
            lab = np.asarray(grin.vertex_prop(label_prop)).astype(np.int32)
        # the Pallas kernel needs the whole slab VMEM-resident; gate on the
        # lane-aligned slab size BEFORE anything is allocated
        W = sample_ell_width(deg)
        if self.use_kernels:
            self.use_kernels = n * W * 4 <= SLAB_VMEM_BYTES

        if self.exchange == "psum":
            # the fragment model is dense per owned row (the §9 ELL
            # convention) — O(N·d_max); refuse absurd builds BEFORE the
            # slab is materialized, with a pointer at the O(E) path
            slab_bytes = F * vp * W * 4
            if slab_bytes > PSUM_SLAB_LIMIT_BYTES:
                raise ValueError(
                    f"psum fragment slab would be {slab_bytes / 2**30:.1f} "
                    f"GiB ([{F}, {vp}, {W}] int32); this graph's "
                    "max degree is too skewed for the dense fragment "
                    "exchange — use exchange='stacked' (O(E) CSR draws) "
                    "or raise repro.engines.sample.PSUM_SLAB_LIMIT_BYTES")
            ell, _ = csr_to_sample_ell(indptr, indices)
            self._W = ell.shape[1]
            # fragment-stacked tables: [F, v_per, ...] owned slices
            f_ell = np.full((F, vp, self._W), PAD_SENTINEL, np.int32)
            f_deg = np.zeros((F, vp), np.int32)
            f_feat = np.zeros((F, vp, self.feature_dim), np.float32)
            f_lab = None if lab is None else np.zeros((F, vp), np.int32)
            f_types = None if vtypes is None else np.zeros((F, vp), np.int32)
            for f in range(F):
                lo, hi = f * vp, min((f + 1) * vp, n)
                if hi <= lo:                    # fragment past the last row
                    continue
                f_ell[f, :hi - lo] = ell[lo:hi]
                f_deg[f, :hi - lo] = deg[lo:hi]
                f_feat[f, :hi - lo] = feats[lo:hi]
                if f_lab is not None:
                    f_lab[f, :hi - lo] = lab[lo:hi]
                if f_types is not None:
                    f_types[f, :hi - lo] = vtypes[lo:hi]
            self.ell = jnp.asarray(f_ell)
            self.deg = jnp.asarray(f_deg)
            # under a mesh the table is resharded inside shard_map
            self.feats = (jnp.asarray(f_feat) if mesh is not None
                          else resident_table(f_feat))
            self.labels = None if f_lab is None else jnp.asarray(f_lab)
            self.types = None if f_types is None else jnp.asarray(f_types)
            self.starts = jnp.arange(F, dtype=jnp.int32) * vp
        else:
            # stacked-reshape fast path: the F fragments ARE rows
            # [0, n) of the flat tables (range partition is contiguous);
            # ids < 0 or ≥ n gather the all-zero pad row n. Draws come
            # straight off CSR at O(E) memory — the dense [N, max_deg]
            # slab (an O(N·d_max) blowup on power-law graphs) is built
            # only for the Pallas-kernel path, which the VMEM gate bounds
            self.deg = jnp.asarray(deg)
            if self.use_kernels:
                ell, _ = csr_to_sample_ell(indptr, indices)
                self.ell = jnp.asarray(ell)
                self.csr_starts = self.csr_indices = None
            else:
                self.ell = None
                self.csr_starts = jnp.asarray(indptr[:-1].astype(np.int32))
                # one trailing sentinel: degree-0 tail rows gather
                # in-bounds (masked by deg == 0 anyway)
                self.csr_indices = jnp.asarray(np.concatenate(
                    [indices, [PAD_SENTINEL]]).astype(np.int32))
            feats_pad = np.zeros((n + 1, self.feature_dim), np.float32)
            feats_pad[:n] = feats
            self.feats = resident_table(feats_pad)
            self.labels = None
            if lab is not None:
                lab_pad = np.zeros(n + 1, np.int32)
                lab_pad[:n] = lab
                self.labels = jnp.asarray(lab_pad)
            self.types = None
            if vtypes is not None:
                self.types = jnp.asarray(np.concatenate([vtypes, [0]]))
        self.rel_types = None if rel_types is None else jnp.asarray(rel_types)
        self._tables = self._make_tables()
        self._jit_sample = jax.jit(self._sample_impl,
                                   static_argnames=("fanouts",))

    def _make_tables(self) -> Dict[str, Optional[jnp.ndarray]]:
        """Device tables as ONE pytree. The jitted batch takes this as an
        argument (never as closure constants), so an ``advance()``d
        executor with patched same-shape tables reuses the compiled
        program — the sampling analogue of the frontier executor's
        arrays-as-args rule (DESIGN.md §15)."""
        tables = {"ell": self.ell, "deg": self.deg, "feats": self.feats,
                  "labels": self.labels,
                  "starts": getattr(self, "starts", None),
                  "csr_starts": getattr(self, "csr_starts", None),
                  "csr_indices": getattr(self, "csr_indices", None)}
        if self.n_relations:
            tables.update(types=self.types, rel_types=self.rel_types)
        return tables

    # ------------------------------------------------------- incremental
    def advance(self, store, delta, pg=None
                ) -> Optional["FragmentSampleExecutor"]:
        """A new executor over ``store`` (the next snapshot) reusing this
        one's device tables and compiled batch program (DESIGN.md §15).

        Sampling slabs must keep rows in NEW-CSR segment order (the draw
        ``floor(u·deg)`` indexes the row), so instead of tail-appending,
        every touched row is rewritten from the already-incrementally-
        merged CSR — O(touched·W) — and the slab widens (one retrace) only
        when a touched vertex's degree outgrows the current lane-aligned
        width; the result is bit-identical to a fresh build. Feature and
        label tables carry over untouched. Returns ``None`` (callers full-
        rebuild) when the lineage check fails, when the delta touched the
        feature/label property, or when the patched slab would cross a
        kernel/psum size gate, or for a typed executor, whose slab holds
        relation codes."""
        from repro.storage.csr import topo_base
        if self.n_relations:
            return None
        if pg is not None:
            store = pg.grin.store
        indptr1, indices1 = (pg.sliced_csr(None, "out")[:2] if pg is not None
                             else store.adjacency())  # triggers the merge
        info = getattr(store, "_inc_info", None)
        old_merged = getattr(self.store, "_merged", self.store)
        if info is None or topo_base(info[0]) is not topo_base(old_merged):
            return None
        _, old_pos, new_pos = info
        touched = (frozenset(delta.vprop_names) if delta is not None
                   else frozenset())
        if self.feature_prop in touched or (
                self.label_prop is not None and self.label_prop in touched):
            return None
        new = FragmentSampleExecutor.__new__(FragmentSampleExecutor)
        for f in ("mesh", "exchange", "n_frags", "v_per", "n_vertices",
                  "n_relations", "use_kernels", "interpret", "feature_dim",
                  "feature_prop", "label_prop", "feats", "labels",
                  "_jit_sample"):
            setattr(new, f, getattr(self, f))
        new.store = store
        if old_pos is None or len(new_pos) == 0:
            # vprops-only commit: identical topology, share every table
            for f in ("ell", "deg", "starts", "csr_starts", "csr_indices",
                      "_W"):
                if hasattr(self, f):
                    setattr(new, f, getattr(self, f))
            new._tables = self._tables
            return new
        if delta is None or len(delta.src) != len(new_pos):
            return None
        deg1 = np.diff(indptr1).astype(np.int32)
        rows_t = np.unique(np.asarray(delta.src, np.int64))
        if self.exchange == "psum" or self.use_kernels:
            W = int(self.ell.shape[-1])
            Wn = max(W, sample_ell_width(deg1))
            if self.use_kernels and self.n_vertices * Wn * 4 > SLAB_VMEM_BYTES:
                return None             # kernel path no longer fits VMEM
            if (self.exchange == "psum" and self.n_frags * self.v_per * Wn
                    * 4 > PSUM_SLAB_LIMIT_BYTES):
                return None
            patch = np.full((len(rows_t), Wn), PAD_SENTINEL, np.int32)
            for i, r in enumerate(rows_t):
                seg = indices1[indptr1[r]:indptr1[r + 1]]
                patch[i, :len(seg)] = seg
            ell = self.ell
            if Wn > W:                  # widen (one retrace), PAD-filled
                pad = [(0, 0)] * (ell.ndim - 1) + [(0, Wn - W)]
                ell = jnp.pad(ell, pad, constant_values=PAD_SENTINEL)
        if self.exchange == "psum":
            fi = rows_t // self.v_per
            li = rows_t - fi * self.v_per
            new.ell = ell.at[fi, li].set(jnp.asarray(patch))
            new.deg = self.deg.at[fi, li].set(jnp.asarray(deg1[rows_t]))
            new.starts = self.starts
            new._W = Wn
        elif self.use_kernels:
            new.ell = ell.at[jnp.asarray(rows_t)].set(jnp.asarray(patch))
            new.deg = self.deg.at[jnp.asarray(rows_t)].set(
                jnp.asarray(deg1[rows_t]))
            new.csr_starts = new.csr_indices = None
        else:
            # CSR-draw path: indptr shifts globally on insert, so this is
            # an O(E) array re-upload — no sort/merge compute, the CSR was
            # already extended incrementally at the storage layer
            new.ell = None
            new.deg = jnp.asarray(deg1)
            new.csr_starts = jnp.asarray(indptr1[:-1].astype(np.int32))
            new.csr_indices = jnp.asarray(np.concatenate(
                [indices1, [PAD_SENTINEL]]).astype(np.int32))
        new._tables = new._make_tables()
        return new

    # ------------------------------------------------------------ one hop
    def _frag_draws(self, ell, deg, start, ids, u):
        """One fragment's exchange contribution: draws for owned frontier
        entries as ``nbr + 1``, 0 elsewhere (psum-combinable)."""
        local = ids - start
        owned = (ids >= 0) & (local >= 0) & (local < self.v_per)
        rows = jnp.where(owned, local, -1).astype(jnp.int32)
        if self.use_kernels:
            nbr = sample_ell(ell, deg, rows, u, interpret=self.interpret)
        else:
            nbr = sample_ell_jnp(ell, deg, rows, u)
        return jnp.where(nbr >= 0, nbr + 1, 0)

    def _layer(self, t: Dict[str, jnp.ndarray], ids: jnp.ndarray,
               u: jnp.ndarray) -> jnp.ndarray:
        """ids [M] global (< 0 ⇒ PAD), u [M, K] → sampled neighbors [M, K]."""
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            def frag_fn(ell, deg, start, ids, u):
                # disjoint owned seeds: psum is the fragment exchange
                # (use_kernels is forced off under a mesh, so _frag_draws
                # runs the jnp form here)
                contrib = self._frag_draws(ell[0], deg[0], start[0], ids, u)
                return jax.lax.psum(contrib, "data")

            fn = jax.shard_map(frag_fn, mesh=self.mesh,
                               in_specs=(P("data"), P("data"), P("data"),
                                         P(), P()),
                               out_specs=P())
            return fn(t["ell"], t["deg"], t["starts"], ids, u) - 1

        if self.exchange == "psum":
            acc = self._frag_draws(t["ell"][0], t["deg"][0], 0, ids, u)
            for f in range(1, self.n_frags):
                acc = acc + self._frag_draws(t["ell"][f], t["deg"][f],
                                             f * self.v_per, ids, u)
            return acc - 1

        # stacked fast path: one draw against the flat tables; out-of-range
        # ids (< 0 or ≥ n) become invalid rows, matching the psum contract
        rows = jnp.where((ids >= 0) & (ids < self.n_vertices), ids,
                         -1).astype(jnp.int32)
        if self.use_kernels:
            return sample_ell(t["ell"], t["deg"], rows, u,
                              interpret=self.interpret)
        return sample_csr_jnp(t["csr_starts"], t["deg"], t["csr_indices"],
                              rows, u)

    # ------------------------------------------------------ feature gather
    def _frag_gather(self, table, start, ids):
        """One fragment's owned rows of a [v_per, ...] sharded table."""
        local = ids - start
        owned = (ids >= 0) & (local >= 0) & (local < self.v_per)
        safe = jnp.clip(local, 0, self.v_per - 1)
        rows = jnp.take(table, safe, axis=0)
        mask = owned.reshape((-1,) + (1,) * (rows.ndim - 1))
        return rows * mask.astype(rows.dtype)

    def _gather(self, table_stacked, ids: jnp.ndarray) -> jnp.ndarray:
        """Cross-fragment gather of sharded per-vertex data (features or
        labels): psum of disjoint owned slices; PAD ids get zero rows. On
        the stacked path the same contract is one padded-row take."""
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            def frag_fn(table, start, ids):
                rows = self._frag_gather(table[0], start[0], ids)
                return jax.lax.psum(rows, "data")

            fn = jax.shard_map(frag_fn, mesh=self.mesh,
                               in_specs=(P("data"), P("data"), P()),
                               out_specs=P())
            # starts is pure fragment-offset config (arange(F)·v_per) —
            # identical for every advance() generation, safe as a constant
            return fn(table_stacked, self.starts, ids)

        if self.exchange == "psum":
            acc = self._frag_gather(table_stacked[0], 0, ids)
            for f in range(1, self.n_frags):
                acc = acc + self._frag_gather(table_stacked[f],
                                              f * self.v_per, ids)
            return acc

        # stacked fast path: invalid ids hit the all-zero pad row n
        safe = jnp.where((ids >= 0) & (ids < self.n_vertices), ids,
                         self.n_vertices).astype(jnp.int32)
        return jnp.take(table_stacked, safe, axis=0)

    def gather_features(self, ids) -> jnp.ndarray:
        """[M] global vertex ids → [M, D] features (0-rows for PAD ids)."""
        return self._gather(self._tables["feats"],
                            jnp.asarray(ids, jnp.int32))

    # ------------------------------------------------------------- batch
    def _sample_impl(self, tables, seeds, key, fanouts: Tuple[int, ...]):
        # device scopes (DESIGN.md §10): a profile names each op by the
        # part of the batch it belongs to, whatever XLA's fusion numbering
        frontiers = [seeds.astype(jnp.int32)]
        layers, rels, types = [], [], []
        for l, k in enumerate(fanouts):
            with jax.named_scope(f"sample.hop{l}"):
                u = layer_uniforms(key, l, frontiers[-1].shape[0], k)
                nbrs = self._layer(tables, frontiers[-1], u)
                if self.n_relations:
                    nbrs, rel = split_codes(nbrs, self.n_relations)
                    rels.append(rel)
                    # a neighbour's type is its relation's source type
                    types.append(jnp.take(tables["rel_types"], jnp.maximum(
                        rel, 0)).reshape(-1))
            layers.append(nbrs)
            frontiers.append(nbrs.reshape(-1))
        with jax.named_scope("gather.features"):
            feats = [self._gather(tables["feats"], fr) for fr in frontiers]
        labels = None
        if tables["labels"] is not None:
            with jax.named_scope("gather.labels"):
                labels = self._gather(tables["labels"], frontiers[0])
        typing = None
        if self.n_relations:
            with jax.named_scope("gather.types"):
                types.insert(0, self._gather(tables["types"], frontiers[0]))
            typing = (rels, types)
        return layers, feats, labels, typing

    def sample(self, seeds, key, fanouts: Sequence[int]):
        """One jitted layered batch: seeds [B] → (layers, feats, labels),
        and for a typed executor (rels, types) after them.

        layers[l]: [B·∏f[:l], f[l]] int32 draws (PAD_SENTINEL for invalid);
        feats[l]: frontier-l features [B·∏f[:l], D]; labels [B] int32 (None
        without a label property); rels[l] the relation of each draw in
        layers[l] (PAD_SENTINEL for invalid); types[l] the node type of
        each frontier-l vertex (a padded entry's is meaningless). All
        device-resident jnp arrays."""
        seeds = jnp.asarray(np.asarray(seeds, np.int32))
        out = self._jit_sample(self._tables, seeds, key,
                               tuple(int(f) for f in fanouts))
        if self.n_relations:
            return out[:3] + out[3]
        return out[:3]
