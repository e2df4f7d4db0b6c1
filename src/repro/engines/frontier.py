"""Fragment-backed OLAP traversal — Gaia plans on the GRAPE substrate
(DESIGN.md §9).

``lower_to_frontier`` (core/ir/codegen.py) turns a plan's match prefix into
dense frontier stages; this executor runs them on the partitioned fragment
model the analytics engine already uses: the hop adjacency is sliced per
(edge_label, direction) from the shared ``PropertyGraph`` caches,
range-partitioned into F fragments of owned *destination* rows, and one
admission batch of B queries executes as ONE jitted device program over a
``[B, N]`` path-count matrix:

    X₀[b, v] = 1 ⇔ v matches query b's anchor
    X ← hop(X) ⊙ mask_hop          (one fused stage per EXPAND/WHERE)
    X[b, v] = #matched paths of query b ending at v

Fragment execution mirrors ``grape/engine.py``: each fragment computes its
owned ``[B, v_per]`` slice, then the slices exchange across the ``data``
mesh axis (``psum`` of disjoint ranges under ``shard_map``; a stacked
reshape on one device). The hop itself is the batched pull-ELL Pallas
kernel (``kernels/frontier.py``) on TPU and a jnp gather/scatter with the
same padding contract (``PAD_SENTINEL``) on CPU. Python-level results come
from ``finish_frontier``: vertex ids repeated by path count, relational
tail on the interpreter — which therefore stays the semantic oracle the
differential tests compare against (``tests/test_traversal.py``,
``tests/test_property.py``).
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ir.codegen import (DeviceTail, FrontierHop, FrontierProgram,
                                   TailDataFallback, _LabelAwarePG,
                                   _expr_has_param, f32_exact_scalar,
                                   finish_device_tail, finish_frontier,
                                   finish_shortest, frontier_vertex_mask,
                                   lower_tail, lower_to_frontier)
from repro.core.ir.dag import BinExpr, Const, LogicalPlan, Param, PropRef
from repro.storage.lpg import PropertyGraph

_F32_INT_LIMIT = 2 ** 24


@dataclasses.dataclass
class _HopArrays:
    """Device-resident adjacency of one (edge_label, direction) hop.

    Edge-list form (all paths): ``src/row/w [F, Ep]`` — global frontier-side
    vertex, local owned destination row, weight (0 ⇒ padding).
    Slab form (kernel path): per-fragment pull-ELL slabs from
    ``csr_to_ell`` with local ``row_map``.

    ``hop`` (the lowering metadata), ``counts`` (host per-fragment used
    entries) and ``slab_meta`` (host per-fragment slab occupancy) exist so
    :meth:`FragmentFrontierExecutor.advance` can append a commit's delta
    edges in place instead of rebuilding the arrays (DESIGN.md §15); the
    jitted runners receive these arrays as *arguments*, so a patched hop
    with unchanged shapes reuses the compiled program."""

    src: jnp.ndarray
    row: jnp.ndarray
    w: jnp.ndarray
    slabs: Optional[List[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]]
    hop: Optional[FrontierHop] = None
    counts: Optional[np.ndarray] = None
    # per fragment: (fill [Np] — entries used per slab row,
    #               last_row [v_local] — slab row holding vertex tail
    #               entries or -1, used — slab rows allocated)
    slab_meta: Optional[List[Tuple[np.ndarray, np.ndarray, int]]] = None

    def args(self, use_kernels: bool):
        """The pytree the jitted runners consume: arrays only, no
        metadata — jit retraces on shape changes, never on patches."""
        if use_kernels:
            return tuple(self.slabs)
        return (self.src, self.row, self.w)


def _expr_prop_names(expr) -> frozenset:
    """Property names a predicate expression reads — what decides whether
    a cached static mask / device prop column survives a commit whose
    delta touched some vertex-property columns."""
    if isinstance(expr, PropRef):
        return frozenset() if expr.prop is None else frozenset([expr.prop])
    if isinstance(expr, BinExpr):
        return _expr_prop_names(expr.left) | _expr_prop_names(expr.right)
    return frozenset()


def _slab_occupancy(local_ptr: np.ndarray, n_slab_rows: int,
                    row_split: int = 1024):
    """Host occupancy of a ``csr_to_ell`` slab: per-slab-row entry counts,
    each local vertex's tail slab row (-1 when degree 0), and the number
    of slab rows in use — what incremental appends consult to place new
    entries into the padding (``csr_to_ell`` rounds slab rows up to a
    block multiple, so spare rows exist below the array bound)."""
    deg = np.diff(local_ptr)
    fill = np.zeros(n_slab_rows, np.int64)
    last_row = np.full(len(deg), -1, np.int64)
    i = 0
    for r, d in enumerate(deg):
        left = int(d)
        while left > 0:
            take = min(left, row_split)
            fill[i] = take
            last_row[r] = i
            left -= take
            i += 1
    return fill, last_row, max(i, 1)    # empty slabs still hold one row


class FragmentFrontierExecutor:
    """Executes lowered ``FrontierProgram``s over F stacked fragments."""

    def __init__(self, pg: PropertyGraph, n_frags: int = 1, mesh=None,
                 use_kernels: bool = False,
                 interpret: Optional[bool] = None,
                 device_tail: bool = True):
        self.pg = pg if isinstance(pg, PropertyGraph) else PropertyGraph(pg)
        self.mesh = mesh
        if mesh is not None:
            if "data" not in mesh.axis_names:
                raise ValueError(
                    "FragmentFrontierExecutor shard_maps fragments over "
                    f"the 'data' mesh axis; mesh has {mesh.axis_names}")
            n_frags = int(mesh.shape["data"])
        self.n_frags = n_frags
        n = self.pg.n_vertices
        self.v_per = -(-n // n_frags)
        # the Pallas slab path needs stacking-free per-fragment dispatch;
        # under a mesh the hop runs the edge-list form inside shard_map
        self.use_kernels = use_kernels and mesh is None
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret
        self.device_tail = device_tail
        self._hops: Dict[Tuple, _HopArrays] = {}
        self._runners: Dict[Tuple, Any] = {}
        # device-tail compilation memo: (head, repr(tail ops)) → DeviceTail
        # or None; validated float32 vertex-property columns (None ⇒ the
        # property cannot ride float32 exactly — data fallback)
        self._tails: Dict[Tuple, Optional[DeviceTail]] = {}
        self._prop_cols: Dict[str, Optional[jnp.ndarray]] = {}
        # static (param-free) [N] stage masks, keyed (label, pred repr),
        # stored with the vprop names they read so advance() knows which
        # survive a commit; rebuilt per execute only when the predicate
        # carries $params
        self._masks: Dict[Tuple, Tuple[jnp.ndarray, frozenset]] = {}
        self._programs: "weakref.WeakKeyDictionary[LogicalPlan, Any]" = \
            weakref.WeakKeyDictionary()

    # ------------------------------------------------------------ lowering
    def program_for(self, plan: LogicalPlan) -> Optional[FrontierProgram]:
        """Lowered program for a (cached) plan object, memoized per plan."""
        try:
            prog = self._programs.get(plan, False)
        except TypeError:                 # unhashable plan, lower fresh
            return lower_to_frontier(plan)
        if prog is False:
            prog = lower_to_frontier(plan)
            self._programs[plan] = prog
        return prog

    # ------------------------------------------------------- hop adjacency
    def _hop_arrays(self, hop: FrontierHop) -> _HopArrays:
        key = hop.cache_key
        cached = self._hops.get(key)
        if cached is not None:
            return cached
        # pull orientation: slab/edge rows are the hop's *destination*
        # vertices, entries the frontier-side sources — so the row range
        # partition assigns each fragment the vertices it owns
        opp = "in" if hop.direction == "out" else "out"
        indptr, indices, emap = self.pg.sliced_csr(hop.edge_label, opp)
        eids = emap if emap is not None \
            else np.arange(len(indices), dtype=np.int64)
        w = np.ones(len(indices), np.float32)
        if hop.edge_pred is not None:
            from repro.core.ir.dag import eval_expr
            keep = eval_expr(hop.edge_pred.expr, {}, _LabelAwarePG(self.pg),
                             {hop.edge_alias: eids})
            w = np.asarray(keep, np.float32)

        F, vp, n = self.n_frags, self.v_per, self.pg.n_vertices
        deg = np.diff(indptr)
        # tiny graphs can leave trailing fragments with no owned rows
        bounds = [(min(f * vp, n), min((f + 1) * vp, n)) for f in range(F)]
        ep = max(1, max(int(indptr[hi] - indptr[lo]) for lo, hi in bounds))
        # capacity slack, rounded to a lane multiple: small commit deltas
        # append into the padding without changing array shapes, so the
        # jitted runners (which take these arrays as arguments) keep their
        # compiled programs across rebinds (DESIGN.md §15). The extra 25%
        # matches the regrow policy — a tight initial fit would force a
        # regrow (and a retrace per batch shape) on the first commit
        ep = -(-max(ep + ep // 4, ep + 128) // 128) * 128
        f_src = np.zeros((F, ep), np.int32)
        f_row = np.zeros((F, ep), np.int32)
        f_w = np.zeros((F, ep), np.float32)      # 0-weight ⇒ padding
        counts = np.zeros(F, np.int64)
        slabs = [] if self.use_kernels else None
        slab_meta = [] if self.use_kernels else None
        for f in range(F):
            lo, hi = bounds[f]
            e_lo, e_hi = int(indptr[lo]), int(indptr[hi])
            ne = e_hi - e_lo
            counts[f] = ne
            f_src[f, :ne] = indices[e_lo:e_hi]
            f_row[f, :ne] = np.repeat(np.arange(hi - lo),
                                      deg[lo:hi]).astype(np.int32)
            f_w[f, :ne] = w[e_lo:e_hi]
            if slabs is not None:
                from repro.kernels.ops import csr_to_ell
                local_ptr = (indptr[lo:hi + 1] - e_lo).astype(np.int64)
                ell_idx, ell_w, row_map = csr_to_ell(
                    local_ptr, indices[e_lo:e_hi].astype(np.int32),
                    w[e_lo:e_hi])
                slabs.append((jnp.asarray(ell_idx), jnp.asarray(ell_w),
                              jnp.asarray(row_map)))
                slab_meta.append(_slab_occupancy(local_ptr, len(row_map)))
        arrs = _HopArrays(src=jnp.asarray(f_src), row=jnp.asarray(f_row),
                          w=jnp.asarray(f_w), slabs=slabs, hop=hop,
                          counts=counts, slab_meta=slab_meta)
        self._hops[key] = arrs
        return arrs

    # ------------------------------------------------------- incremental
    def advance(self, new_pg, delta
                ) -> Optional["FragmentFrontierExecutor"]:
        """A new executor over ``new_pg`` carrying this one's device state
        and compiled programs across ONE commit (DESIGN.md §15).

        Hop adjacency is patched copy-on-write — delta edges append into
        the capacity slack of fresh arrays, the old executor's arrays are
        never mutated (in-flight fast-lane batches and pinned readers keep
        their epoch). Because every jitted runner takes the hop arrays as
        call arguments, the shared ``_runners`` cache keeps its compiled
        programs whenever shapes hold (the dominant rebind cost). Static
        masks and device prop columns survive unless the delta touched a
        vertex-property they read. Returns ``None`` when the lineage check
        fails (``new_pg``'s merged CSR was not extended from this
        executor's graph) — callers build a fresh executor instead.

        Memory note: runner closures retain the executor generation that
        first traced them; retention is bounded by distinct program
        shapes, not by commit count."""
        new_pg = new_pg if isinstance(new_pg, PropertyGraph) \
            else PropertyGraph(new_pg)
        from repro.storage.csr import topo_base
        info = getattr(new_pg.grin.store, "_inc_info", None)
        old_store = self.pg.grin.store
        old_merged = getattr(old_store, "_merged", old_store)
        if info is None or topo_base(info[0]) is not topo_base(old_merged):
            return None
        _, old_pos, new_pos = info
        if old_pos is not None and (delta is None
                                    or len(delta.src) != len(new_pos)):
            return None
        new = FragmentFrontierExecutor.__new__(FragmentFrontierExecutor)
        new.pg = new_pg
        new.mesh = self.mesh
        new.n_frags = self.n_frags
        new.v_per = self.v_per          # vertex count never changes
        new.use_kernels = self.use_kernels
        new.interpret = self.interpret
        new.device_tail = self.device_tail
        new._runners = self._runners    # arrays are args: programs carry
        new._tails = self._tails        # structural, data-independent
        new._programs = self._programs  # plan → lowering, data-independent
        touched = (frozenset(delta.vprop_names) if delta is not None
                   else frozenset())
        new._masks = {k: v for k, v in self._masks.items()
                      if not (v[1] & touched)}
        new._prop_cols = {k: v for k, v in self._prop_cols.items()
                          if k not in touched}
        if old_pos is None or len(new_pos) == 0:
            # vprops-only commit: identical topology, share every hop
            new._hops = dict(self._hops)
            return new
        new._hops = {}
        for key, arrs in self._hops.items():
            patched = new._patch_hop(arrs, delta, new_pos)
            if patched is not None:
                new._hops[key] = patched
        return new

    def _patch_hop(self, arrs: _HopArrays, delta,
                   new_pos: np.ndarray) -> Optional[_HopArrays]:
        """Append one delta's same-label edges to a hop's device arrays.
        Scatter-add/scatter-min hops are order-insensitive within a
        fragment, so new entries simply land at the used-entry tail; the
        arrays only regrow (one retrace) when the slack runs out."""
        hop = arrs.hop
        if hop is None:
            return None
        keep = (np.ones(len(delta.src), bool) if hop.edge_label is None
                else delta.labels == hop.edge_label)
        if not keep.any():
            return arrs                 # untouched: share (never mutated)
        d_src = delta.src[keep]
        d_dst = delta.dst[keep]
        if hop.edge_pred is not None:
            from repro.core.ir.dag import eval_expr
            ok = eval_expr(hop.edge_pred.expr, {}, _LabelAwarePG(self.pg),
                           {hop.edge_alias: new_pos[keep]})
            w_new = np.asarray(ok, np.float32)
        else:
            w_new = np.ones(len(d_src), np.float32)
        opp = "in" if hop.direction == "out" else "out"
        rows = (d_dst if opp == "in" else d_src).astype(np.int64)
        ents = (d_src if opp == "in" else d_dst).astype(np.int64)
        F, vp = self.n_frags, self.v_per
        k = len(rows)
        fo = rows // vp
        order = np.argsort(fo, kind="stable")
        fo_s, rows_s = fo[order], rows[order]
        ents_s, w_s = ents[order], w_new[order]
        per_f = np.bincount(fo_s, minlength=F)
        starts = np.cumsum(per_f) - per_f
        within = np.arange(k) - starts[fo_s]
        counts1 = arrs.counts + per_f
        ep = int(arrs.src.shape[1])
        # keep one spare slot in fragment 0: bucket-padded scatter
        # entries (below) park there as w=0 no-ops
        need = int(max(counts1.max(), counts1[0] + 1))
        if need > ep:                   # regrow with slack (one retrace)
            ep1 = -(-max(need, ep + ep // 4) // 128) * 128
        else:
            ep1 = ep
        src1, row1, w1 = arrs.src, arrs.row, arrs.w
        if ep1 != ep:
            pad = ((0, 0), (0, ep1 - ep))
            src1 = jnp.pad(src1, pad)
            row1 = jnp.pad(row1, pad)
            w1 = jnp.pad(w1, pad)
        cols = arrs.counts[fo_s] + within
        # bucket-pad the scatter operands to a power-of-two length: the
        # device scatter is compiled per operand shape, and delta sizes
        # vary every commit — without the buckets each commit pays a
        # fresh XLA compile. Padded entries write (0, 0, w=0) — the
        # padding contract — into fragment 0's first unused slot.
        rowv = (rows_s - fo_s * vp).astype(np.int32)
        bucket = 1 << max(7, int(k - 1).bit_length())
        if bucket > k:
            pn = bucket - k
            fo_p = np.concatenate([fo_s, np.zeros(pn, fo_s.dtype)])
            cols_p = np.concatenate([cols,
                                     np.full(pn, int(counts1[0]),
                                             cols.dtype)])
            ents_p = np.concatenate([ents_s.astype(np.int32),
                                     np.zeros(pn, np.int32)])
            rowv_p = np.concatenate([rowv, np.zeros(pn, np.int32)])
            w_p = np.concatenate([w_s, np.zeros(pn, np.float32)])
        else:
            fo_p, cols_p, w_p = fo_s, cols, w_s
            ents_p, rowv_p = ents_s.astype(np.int32), rowv
        src1 = src1.at[fo_p, cols_p].set(jnp.asarray(ents_p))
        row1 = row1.at[fo_p, cols_p].set(jnp.asarray(rowv_p))
        w1 = w1.at[fo_p, cols_p].set(jnp.asarray(w_p))
        slabs1 = meta1 = None
        if self.use_kernels:
            slabs1, meta1 = list(arrs.slabs), list(arrs.slab_meta)
            for f in np.unique(fo_s):
                sel = fo_s == f
                if not self._patch_slab(slabs1, meta1, int(f),
                                        rows_s[sel] - int(f) * vp,
                                        ents_s[sel], w_s[sel]):
                    self._rebuild_slab(slabs1, meta1, int(f), hop, opp)
        return _HopArrays(src=src1, row=row1, w=w1, slabs=slabs1, hop=hop,
                          counts=counts1, slab_meta=meta1)

    def _patch_slab(self, slabs, meta, f: int, l_rows, ents, w_new) -> bool:
        """Grow one fragment's pull-ELL slab in place: entries append into
        the tail slab row of their vertex; rows that run out of width get
        a fresh slab row from the block-alignment spare region (the
        scatter-add reduction over ``row_map`` is grouping-insensitive).
        Returns False when the spare rows are exhausted — caller rebuilds
        the fragment's slab."""
        ell_idx, ell_w, row_map = slabs[f]
        fill, last_row, used = meta[f]
        n_slab, W = ell_idx.shape
        fill, last_row = fill.copy(), last_row.copy()
        pos_r = np.empty(len(ents), np.int64)
        pos_c = np.empty(len(ents), np.int64)
        fresh_rows: Dict[int, int] = {}
        for i, r in enumerate(np.asarray(l_rows, np.int64)):
            lr = int(last_row[r])
            if lr < 0 or fill[lr] >= W:
                if used >= n_slab:
                    return False
                lr = used
                used += 1
                fresh_rows[lr] = int(r)
                last_row[r] = lr
            pos_r[i], pos_c[i] = lr, fill[lr]
            fill[lr] += 1
        idx1 = ell_idx.at[pos_r, pos_c].set(
            jnp.asarray(ents.astype(np.int32)))
        w1 = ell_w.at[pos_r, pos_c].set(jnp.asarray(w_new))
        rm = row_map
        if fresh_rows:
            rm = row_map.at[np.fromiter(fresh_rows, np.int64)].set(
                jnp.asarray(np.fromiter(fresh_rows.values(), np.int64)))
        slabs[f] = (idx1, w1, rm)
        meta[f] = (fill, last_row, used)
        return True

    def _rebuild_slab(self, slabs, meta, f: int, hop, opp: str) -> None:
        """Spare slab rows ran out: rebuild ONE fragment's slab from the
        (already incrementally-patched) label slice."""
        from repro.kernels.ops import csr_to_ell
        indptr, indices, emap = self.pg.sliced_csr(hop.edge_label, opp)
        n, vp = self.pg.n_vertices, self.v_per
        lo, hi = min(f * vp, n), min((f + 1) * vp, n)
        e_lo, e_hi = int(indptr[lo]), int(indptr[hi])
        if hop.edge_pred is not None:
            from repro.core.ir.dag import eval_expr
            eids = (emap if emap is not None
                    else np.arange(len(indices), dtype=np.int64))
            ok = eval_expr(hop.edge_pred.expr, {}, _LabelAwarePG(self.pg),
                           {hop.edge_alias: eids[e_lo:e_hi]})
            wseg = np.asarray(ok, np.float32)
        else:
            wseg = np.ones(e_hi - e_lo, np.float32)
        local_ptr = (indptr[lo:hi + 1] - e_lo).astype(np.int64)
        ell_idx, ell_w, row_map = csr_to_ell(
            local_ptr, indices[e_lo:e_hi].astype(np.int32), wseg)
        slabs[f] = (jnp.asarray(ell_idx), jnp.asarray(ell_w),
                    jnp.asarray(row_map))
        meta[f] = _slab_occupancy(local_ptr, len(row_map))

    # ---------------------------------------------------------- device hop
    def _owned_edges(self, src, row, w, x):
        """One fragment, edge-list form: [B, N] → owned [B, v_per]."""
        vals = jnp.take(x, src, axis=1) * w              # [B, Ep]
        return jnp.zeros((x.shape[0], self.v_per),
                         jnp.float32).at[:, row].add(vals)

    def _owned_slab(self, slab, x):
        """One fragment, pull-ELL Pallas kernel (DESIGN.md §2 balance)."""
        from repro.kernels.ops import frontier_step
        ell_idx, ell_w, row_map = slab
        return frontier_step(ell_idx, ell_w, x, row_map, self.v_per,
                             interpret=self.interpret)

    def _apply_hop(self, hop_args, x: jnp.ndarray) -> jnp.ndarray:
        """One hop over the fragment set. ``hop_args`` is the array pytree
        from :meth:`_HopArrays.args` — passed INTO the jitted runners as an
        argument (never closed over), so a rebind that patched the arrays
        in place hits the same compiled program (DESIGN.md §15)."""
        n = self.pg.n_vertices
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            a_src, a_row, a_w = hop_args
            B = x.shape[0]
            npad = self.n_frags * self.v_per
            starts = jnp.arange(self.n_frags, dtype=jnp.int32) * self.v_per

            def frag_fn(src, row, w, start, xr):
                owned = self._owned_edges(src[0], row[0], w[0], xr)
                buf = jax.lax.dynamic_update_slice(
                    jnp.zeros((B, npad), jnp.float32), owned, (0, start[0]))
                # disjoint owned ranges: psum is the fragment exchange,
                # and its result is identical on every chip
                return jax.lax.psum(buf, "data")

            fn = jax.shard_map(frag_fn, mesh=self.mesh,
                               in_specs=(P("data"), P("data"), P("data"),
                                         P("data"), P()),
                               out_specs=P())
            return fn(a_src, a_row, a_w, starts, x)[:, :n]

        if self.use_kernels:
            owned = [self._owned_slab(hop_args[f], x)
                     for f in range(self.n_frags)]
        else:
            a_src, a_row, a_w = hop_args
            owned = [self._owned_edges(a_src[f], a_row[f], a_w[f], x)
                     for f in range(self.n_frags)]
        return jnp.concatenate(owned, axis=1)[:, :n]

    def _owned_edges_minplus(self, src, row, w, d):
        """One fragment, edge-list form, tropical semiring: [B, N]
        distances → owned [B, v_per] relaxations (scatter-min; padding
        entries carry w == 0 and relax to +inf)."""
        vals = jnp.where(w > 0, jnp.take(d, src, axis=1) + 1.0, jnp.inf)
        return jnp.full((d.shape[0], self.v_per), jnp.inf,
                        jnp.float32).at[:, row].min(vals)

    def _owned_slab_minplus(self, slab, d):
        """One fragment, min-plus pull-ELL Pallas kernel."""
        from repro.kernels.ops import frontier_minplus_step
        ell_idx, ell_w, row_map = slab
        return frontier_minplus_step(ell_idx, ell_w, d, row_map, self.v_per,
                                     interpret=self.interpret)

    def _apply_hop_minplus(self, hop_args, d: jnp.ndarray) -> jnp.ndarray:
        """One shortest-path relaxation (before the ``min(d, ·)`` merge).
        Same fragment structure as ``_apply_hop``, but owned slices start
        at +inf and the cross-fragment exchange is ``pmin`` of the disjoint
        owned ranges (DESIGN.md §13)."""
        n = self.pg.n_vertices
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            a_src, a_row, a_w = hop_args
            B = d.shape[0]
            npad = self.n_frags * self.v_per
            starts = jnp.arange(self.n_frags, dtype=jnp.int32) * self.v_per

            def frag_fn(src, row, w, start, dr):
                owned = self._owned_edges_minplus(src[0], row[0], w[0], dr)
                buf = jax.lax.dynamic_update_slice(
                    jnp.full((B, npad), jnp.inf, jnp.float32), owned,
                    (0, start[0]))
                # disjoint owned ranges filled with +inf: pmin exchanges
                return jax.lax.pmin(buf, "data")

            fn = jax.shard_map(frag_fn, mesh=self.mesh,
                               in_specs=(P("data"), P("data"), P("data"),
                                         P("data"), P()),
                               out_specs=P())
            return fn(a_src, a_row, a_w, starts, d)[:, :n]

        if self.use_kernels:
            owned = [self._owned_slab_minplus(hop_args[f], d)
                     for f in range(self.n_frags)]
        else:
            a_src, a_row, a_w = hop_args
            owned = [self._owned_edges_minplus(a_src[f], a_row[f],
                                               a_w[f], d)
                     for f in range(self.n_frags)]
        return jnp.concatenate(owned, axis=1)[:, :n]

    def _hop_args_for(self, program: FrontierProgram):
        """The per-hop array pytrees one execution passes to its runner."""
        return tuple(self._hop_arrays(h).args(self.use_kernels)
                     for h in program.hops)

    def _prefix_fn(self, program: FrontierProgram):
        """The traceable prefix body shared by the plain runner and the
        fused prefix+tail runner. Hop ARRAYS arrive as the ``hops``
        argument — only the static per-hop structure (min/max repeats,
        which is part of every runner cache key) is closed over, so the
        compiled program survives rebinds that patch the adjacency."""
        hop_ranges = [(h.min_hops, h.max_hops) for h in program.hops]

        def run(x, masks, hops):
            # peak accumulation value across var-length stages: float32
            # path counts are exact only below 2^24, and powered stages
            # reach it far sooner than fixed chains — the executor raises
            # OverflowError when the peak crosses it (DESIGN.md §13)
            peak = jnp.float32(0.0)
            for (lo, hi), m, ha in zip(hop_ranges, masks, hops):
                if (lo, hi) == (1, 1):
                    x = self._apply_hop(ha, x)
                else:
                    # accumulated powered stages: acc = Σ_{k∈[lo,hi]} X·Aᵏ
                    # (X itself when lo == 0); intermediate powers below
                    # lo still feed later ones, so their peaks count too
                    acc = x if lo == 0 else jnp.zeros_like(x)
                    cur = x
                    for k in range(1, hi + 1):
                        cur = self._apply_hop(ha, cur)
                        peak = jnp.maximum(peak, jnp.max(cur))
                        if k >= lo:
                            acc = acc + cur
                    peak = jnp.maximum(peak, jnp.max(acc))
                    x = acc
                if m is not None:       # [N] static or [B, N] per-query
                    x = x * m
            return x, peak

        return run

    def _runner(self, program: FrontierProgram):
        skey = tuple((h.cache_key, h.min_hops, h.max_hops)
                     for h in program.hops)
        fn = self._runners.get(skey)
        if fn is not None:
            return fn
        fn = jax.jit(self._prefix_fn(program))
        self._runners[skey] = fn
        return fn

    # ---------------------------------------------------------- device tail
    def _device_tail(self, program: FrontierProgram) -> Optional[DeviceTail]:
        """Structural tail eligibility, memoized per (head, tail) shape."""
        key = (program.head, repr(program.tail))
        if key not in self._tails:
            self._tails[key] = lower_tail(program)
        return self._tails[key]

    def _tail_prop(self, name: str) -> jnp.ndarray:
        """A vertex-property column as a device float32 vector, or
        :class:`TailDataFallback` when the data cannot ride float32
        exactly (non-integer dtype or magnitudes at/above 2²⁴). The
        verdict is cached — same policy as the static mask cache."""
        if name not in self._prop_cols:
            lpg = _LabelAwarePG(self.pg)
            try:
                raw = np.asarray(lpg.vprop(name))
            except KeyError:
                # unknown property: the interpreter tail raises the real
                # KeyError — don't mask it behind a device artifact
                self._prop_cols[name] = None
            else:
                col = None
                if np.issubdtype(raw.dtype, np.integer) \
                        or raw.dtype == np.bool_:
                    if raw.size == 0 or \
                            np.abs(raw).max() < _F32_INT_LIMIT:
                        col = jnp.asarray(raw.astype(np.float32))
                self._prop_cols[name] = col
        col = self._prop_cols[name]
        if col is None:
            raise TailDataFallback(
                f"vertex property {name!r} is not exactly float32-"
                f"representable (need integer/bool dtype, |v| < 2^24)")
        return col

    def _tail_pvals(self, tail: DeviceTail, params_list
                    ) -> Dict[str, jnp.ndarray]:
        """Per-query [B, 1] float32 columns for the tail's $params; any
        value float32 cannot carry exactly falls back (a comparison
        against an inexact constant could flip)."""
        pvals: Dict[str, jnp.ndarray] = {}
        for name in tail.param_names:
            col = np.empty((len(params_list), 1), np.float32)
            for b, p in enumerate(params_list):
                if name not in p or not f32_exact_scalar(p[name]):
                    raise TailDataFallback(
                        f"parameter ${name} missing or not exactly "
                        f"float32-representable")
                col[b, 0] = float(p[name])
            pvals[name] = jnp.asarray(col)
        return pvals

    def _tail_runner(self, program: FrontierProgram, tail: DeviceTail):
        """The fused prefix+tail jitted program (DESIGN.md §14): one trace
        runs the match prefix AND the relational tail — WHERE as frontier
        masks, aggregates as dense reductions over the [B, N] counts,
        ORDER BY as a stable masked argsort — returning only the small
        per-query views ``finish_device_tail`` assembles rows from.

        Exactness is certified inside the trace: ``tail_peak`` tracks the
        magnitude of every arithmetic intermediate (masked to candidate
        lanes) plus the absolute-sum bound of each float32 accumulation;
        the caller discards the device tail and finishes on the
        interpreter when it reaches 2²⁴."""
        skey = ("__tail__",
                tuple((h.cache_key, h.min_hops, h.max_hops)
                      for h in program.hops),
                program.head, repr(tail))
        fn = self._runners.get(skey)
        if fn is not None:
            return fn
        if self.pg.n_vertices >= _F32_INT_LIMIT:
            raise TailDataFallback(
                "vertex ids exceed float32 exact-integer range")
        prefix = self._prefix_fn(program)
        head = program.head
        n = self.pg.n_vertices
        agg_fns = {a.name: a.fn for a in tail.aggs}

        def dev(e, ctx, base):
            """Device eval → (value, peak): value is [N] / [B, 1] / [B, N]
            float32 (bool for predicates); peak bounds |v| of every
            arithmetic node over base-candidate lanes."""
            zero = jnp.float32(0.0)
            if isinstance(e, PropRef):
                if e.prop is not None:
                    return ctx["props"][e.prop], zero
                if e.alias == head:
                    # traced iota, not a captured [N] constant
                    return jnp.arange(n, dtype=jnp.float32), zero
                return ctx["aggs"][e.alias], zero
            if isinstance(e, Const):
                return jnp.float32(float(e.value)), zero
            if isinstance(e, Param):
                return ctx["pvals"][e.name], zero
            lv, lp = dev(e.left, ctx, base)
            if e.op == "in":
                vals = np.asarray([float(v) for v in e.right.value],
                                  np.float32)
                if vals.size == 0:
                    return jnp.zeros_like(lv, bool) & base, lp
                hit = jnp.any(lv[..., None] == jnp.asarray(vals), axis=-1)
                return hit, lp
            rv, rp = dev(e.right, ctx, base)
            peak = jnp.maximum(lp, rp)
            if e.op in ("+", "-", "*"):
                v = {"+": lv + rv, "-": lv - rv, "*": lv * rv}[e.op]
                peak = jnp.maximum(peak, jnp.max(
                    jnp.abs(jnp.where(base, v, 0.0)), initial=0.0))
                return v, peak
            if e.op == "and":
                return jnp.logical_and(lv, rv), peak
            if e.op == "or":
                return jnp.logical_or(lv, rv), peak
            cmp = {"==": lv == rv, "!=": lv != rv, "<": lv < rv,
                   "<=": lv <= rv, ">": lv > rv, ">=": lv >= rv}[e.op]
            return cmp, peak

        def run_tail(x, masks, pvals, hops, props):
            counts, peak = prefix(x, masks, hops)
            cand0 = counts > 0.5
            ctx: Dict[str, Any] = {"pvals": pvals, "aggs": {},
                                   "props": props}
            tpeak = jnp.float32(0.0)
            out: Dict[str, Any] = {"counts": counts, "peak": peak}
            if tail.kind == "scalar":
                xm = jnp.where(cand0, counts, 0.0)
                evs = {}
                for a in tail.aggs:
                    if a.fn == "count":
                        continue
                    ev, p = dev(a.expr, ctx, cand0)
                    tpeak = jnp.maximum(tpeak, p)
                    evs[a.name] = ev
                names = [a.name for a in tail.aggs if a.fn != "count"]
                aggs_out: Dict[str, Any] = {}
                if self.use_kernels and names and all(
                        evs[nm].ndim == 1 for nm in names):
                    from repro.kernels.ops import tail_reduce
                    vals = jnp.stack([evs[nm] for nm in names])
                    cnt, sums, sabs, mins, maxs = tail_reduce(
                        xm, vals, interpret=self.interpret)
                    for j, nm in enumerate(names):
                        fn_ = agg_fns[nm]
                        if fn_ in ("sum", "avg"):
                            aggs_out[nm] = sums[:, j]
                            tpeak = jnp.maximum(tpeak, jnp.max(
                                sabs[:, j], initial=0.0))
                        else:
                            aggs_out[nm] = (mins if fn_ == "min"
                                            else maxs)[:, j]
                else:
                    cnt = jnp.sum(xm, axis=1)
                    for nm in names:
                        fn_ = agg_fns[nm]
                        if fn_ in ("sum", "avg"):
                            term = jnp.where(cand0, counts * evs[nm], 0.0)
                            aggs_out[nm] = jnp.sum(term, axis=1)
                            # Σ m·|e| bounds every partial sum, so below
                            # 2^24 the f32 accumulation is exact in any
                            # association order
                            tpeak = jnp.maximum(tpeak, jnp.max(
                                jnp.sum(jnp.abs(term), axis=1),
                                initial=0.0))
                        elif fn_ == "min":
                            aggs_out[nm] = jnp.min(
                                jnp.where(cand0, evs[nm], jnp.inf), axis=1)
                        else:
                            aggs_out[nm] = jnp.max(
                                jnp.where(cand0, evs[nm], -jnp.inf),
                                axis=1)
                tpeak = jnp.maximum(tpeak, jnp.max(cnt, initial=0.0))
                out["cnt"], out["has_rows"] = cnt, cnt > 0.5
                out["aggs"] = aggs_out
                out["tail_peak"] = tpeak
                return out
            if tail.kind == "group":
                aggs_out = {}
                for a in tail.aggs:
                    if a.fn == "count":
                        ctx["aggs"][a.name] = counts
                        continue
                    ev, p = dev(a.expr, ctx, cand0)
                    tpeak = jnp.maximum(tpeak, p)
                    if a.fn == "sum":
                        col = jnp.where(cand0, counts * ev, 0.0)
                        tpeak = jnp.maximum(tpeak, jnp.max(
                            jnp.abs(col), initial=0.0))
                    else:
                        # min/max/avg of a group whose rows all share the
                        # head vertex: the expr's single distinct value
                        col = jnp.where(cand0, ev, 0.0)
                    ctx["aggs"][a.name] = col
                    aggs_out[a.name] = col
                out["aggs"] = aggs_out
            cand = cand0
            for hx in tail.having:
                hv, hp = dev(hx, ctx, cand0)
                tpeak = jnp.maximum(tpeak, hp)
                cand = jnp.logical_and(cand, hv)
            out["cand"] = cand
            if tail.order_key is not None:
                kv, kp = dev(tail.order_key, ctx, cand0)
                tpeak = jnp.maximum(tpeak, kp)
                from repro.kernels.ops import masked_order
                out["order"] = masked_order(
                    jnp.broadcast_to(kv, counts.shape), cand)
            out["tail_peak"] = tpeak
            return out

        fn = jax.jit(run_tail)
        self._runners[skey] = fn
        return fn

    def _finish_tail(self, program: FrontierProgram, tail: DeviceTail,
                     outd: Dict[str, Any], counts: np.ndarray, params_list
                     ) -> List[Dict[str, np.ndarray]]:
        """Per-query host assembly of the device-tail outputs."""
        aggs = {k: np.asarray(v) for k, v in outd.get("aggs", {}).items()}
        cand = np.asarray(outd["cand"]) if "cand" in outd else None
        order = np.asarray(outd["order"]) if "order" in outd else None
        cnt = np.asarray(outd["cnt"]) if "cnt" in outd else None
        has = np.asarray(outd["has_rows"]) if "has_rows" in outd else None
        res = []
        for b, params in enumerate(params_list):
            view: Dict[str, Any] = {"counts": counts[b],
                                    "aggs": {k: v[b] for k, v in
                                             aggs.items()}}
            if cand is not None:
                view["cand"] = cand[b]
            if order is not None:
                view["order"] = order[b]
            if cnt is not None:
                view["cnt"], view["has_rows"] = cnt[b], has[b]
            res.append(finish_device_tail(program, tail, view, self.pg,
                                          params=params))
        return res

    def _shortest_hop(self, sp) -> FrontierHop:
        return FrontierHop(
            edge_label=sp.edge_label, direction=sp.direction,
            edge_pred=None, edge_alias=None, vertex_alias=sp.alias,
            vertex_label=None, vertex_pred=None)

    def _shortest_runner(self, sp):
        skey = ("__shortest__", sp.edge_label, sp.direction,
                sp.min_hops, sp.max_hops)
        fn = self._runners.get(skey)
        if fn is not None:
            return fn

        def run(d, mask, ha):
            # d ← min(d, relax(d)) unrolled; min_hops == 1 seeds from the
            # first relaxation so dist 0 never enters (src→src must cycle)
            if sp.min_hops >= 1:
                d = self._apply_hop_minplus(ha, d)
                iters = sp.max_hops - 1
            else:
                iters = sp.max_hops
            for _ in range(iters):
                d = jnp.minimum(d, self._apply_hop_minplus(ha, d))
            if mask is not None:        # head label/pred: unreachable = inf
                d = jnp.where(mask > 0, d, jnp.inf)
            return d

        fn = jax.jit(run)
        self._runners[skey] = fn
        return fn

    # -------------------------------------------------------------- execute
    def execute(self, plan: LogicalPlan,
                params_list: Sequence[Optional[Dict[str, Any]]],
                procedures=None) -> List[Dict[str, np.ndarray]]:
        """Run one admission batch (same template, per-query params) as one
        device program; raises ValueError when the plan does not lower.

        The batch is padded to a power-of-two width (repeating the last
        query; its rows are sliced off the result) so the [B, N] program
        shapes repeat across admission chunks — under a sustained mixed
        stream every chunk carries a different handful of same-template
        queries, and without the buckets each distinct B pays its own
        XLA compile."""
        if not params_list:
            return []
        B0 = len(params_list)
        bucket = 1 << max(0, int(B0 - 1).bit_length())
        if bucket > B0:
            params_list = list(params_list) \
                + [params_list[-1]] * (bucket - B0)
        return self._execute_batch(plan, params_list, procedures)[:B0]

    def _execute_batch(self, plan: LogicalPlan,
                       params_list: Sequence[Optional[Dict[str, Any]]],
                       procedures=None) -> List[Dict[str, np.ndarray]]:
        program = plan if isinstance(plan, FrontierProgram) \
            else self.program_for(plan)
        if program is None:
            raise ValueError("plan has no fragment-executable prefix; "
                             "route it to the interpreter instead "
                             "(cbo.should_use_fragment_path gates this)")
        params_list = [p or {} for p in params_list]
        if program.shortest is not None:
            return self._execute_shortest(program, params_list, procedures)
        B, n = len(params_list), self.pg.n_vertices
        src = self._stage_mask(program.source_alias, program.source_label,
                               program.source_pred, params_list)
        if src is None:                      # unfiltered scan: all vertices
            x0 = jnp.ones((B, n), jnp.float32)
        else:
            x0 = jnp.broadcast_to(src, (B, n)).astype(jnp.float32)
        masks = tuple(
            self._stage_mask(h.vertex_alias, h.vertex_label, h.vertex_pred,
                             params_list)
            for h in program.hops)
        hops = self._hop_args_for(program)
        tail = self._device_tail(program) if self.device_tail \
            and program.tail else None
        if tail is not None:
            try:
                pvals = self._tail_pvals(tail, params_list)
                props = {p: self._tail_prop(p) for p in tail.prop_refs}
                outd = self._tail_runner(program, tail)(
                    x0, masks, pvals, hops, props)
            except TailDataFallback:
                outd = None            # data can't ride f32: interpreter tail
            if outd is not None:
                counts = np.asarray(outd["counts"])
                if float(outd["peak"]) >= 2 ** 24 \
                        or counts.max(initial=0.0) >= 2 ** 24:
                    # prefix counts themselves are inexact — the same
                    # contract finish_frontier enforces: the serving layer
                    # catches OverflowError and reruns on the interpreter
                    raise OverflowError(
                        f"frontier path count exceeds float32 exact-integer "
                        f"range (2^24); rerun on the interpreter")
                if float(outd["tail_peak"]) < 2 ** 24:
                    return self._finish_tail(program, tail, outd, counts,
                                             params_list)
                # tail arithmetic overflowed but the counts are exact:
                # finish through the interpreter tail, no device re-run
                return [finish_frontier(program, counts[b], self.pg,
                                        params=params_list[b],
                                        procedures=procedures)
                        for b in range(B)]
        counts, peak = self._runner(program)(x0, masks, hops)
        if float(peak) >= 2 ** 24:
            # same contract as finish_frontier's final check, but covers
            # intermediate powers of accumulated var-length stages whose
            # inexact counts may not survive into the final frontier
            raise OverflowError(
                f"frontier path count {float(peak):.0f} exceeds float32 "
                f"exact-integer range (2^24); rerun on the interpreter")
        counts = np.asarray(counts)
        return [finish_frontier(program, counts[b], self.pg,
                                params=params_list[b], procedures=procedures)
                for b in range(B)]

    def _execute_shortest(self, program: FrontierProgram, params_list,
                          procedures=None) -> List[Dict[str, np.ndarray]]:
        """shortestPath() batch: one [R, N] tropical distance matrix over
        the R flattened (query, source) pairs, relaxed max_hops times."""
        sp = program.shortest
        B, n = len(params_list), self.pg.n_vertices
        src = self._stage_mask(program.source_alias, program.source_label,
                               program.source_pred, params_list)
        if src is None:
            m = np.ones((B, n), bool)
        else:
            ms = np.asarray(src) > 0
            m = np.broadcast_to(ms, (B, n)) if ms.ndim == 1 else ms
        qidx, srcs = np.nonzero(m)
        R = len(srcs)
        if R * n > (1 << 26):
            raise OverflowError(
                f"shortestPath frontier too large ({R} sources x "
                f"{n} vertices); rerun on the interpreter")
        head = self._stage_mask(sp.alias, sp.vertex_label, sp.vertex_pred,
                                params_list)
        hm_rows = None
        if head is not None and R:
            hm = np.asarray(head)
            hm_rows = jnp.asarray(hm[qidx] if hm.ndim == 2
                                  else np.broadcast_to(hm, (R, n)))
        if R == 0:
            dists = np.zeros((0, n), np.float32)
        else:
            d0 = np.full((R, n), np.inf, np.float32)
            d0[np.arange(R), srcs] = 0.0
            runner = self._shortest_runner(sp)
            ha = self._hop_arrays(self._shortest_hop(sp)) \
                .args(self.use_kernels)
            dists = np.asarray(runner(jnp.asarray(d0), hm_rows, ha))
        return [finish_shortest(program, srcs[qidx == b], dists[qidx == b],
                                self.pg, params=params_list[b],
                                procedures=procedures)
                for b in range(B)]

    def _stage_mask(self, alias: str, label: Optional[int], pred,
                    params_list: Sequence[Dict[str, Any]]):
        """One stage's device mask: None when the stage filters nothing,
        a cached static [N] array when the predicate is param-free, a
        per-query [B, N] array otherwise."""
        if label is None and pred is None:
            return None
        if pred is None or not _expr_has_param(pred.expr):
            key = (label, repr(pred))
            cached = self._masks.get(key)
            if cached is None:
                mask = jnp.asarray(frontier_vertex_mask(
                    alias, label, pred, self.pg,
                    params_list[0] if params_list else {}
                ).astype(np.float32))
                # the prop names alongside the mask decide survival under
                # incremental rebind: vertex labels never change, so a
                # mask is stale only when its predicate reads a vprop
                # column the commit delta touched
                names = (_expr_prop_names(pred.expr) if pred is not None
                         else frozenset())
                cached = (mask, names)
                self._masks[key] = cached
            return cached[0]
        B, n = len(params_list), self.pg.n_vertices
        out = np.empty((B, n), np.float32)
        for b, params in enumerate(params_list):
            out[b] = frontier_vertex_mask(alias, label, pred, self.pg,
                                          params).astype(np.float32)
        return jnp.asarray(out)
