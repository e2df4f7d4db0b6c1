"""GRAPE — distributed analytical engine (paper §6), TPU-idiomatic.

Fragment execution follows the paper's design translated to JAX:

- fragments are stacked dense arrays ``[F, ...]`` (partition.py) distributed
  with ``shard_map`` over the ``data`` mesh axis (or ``vmap`` on one device);
- per superstep each fragment combines its out-edge contributions into ONE
  dense length-N message buffer, locally, BEFORE a single
  ``psum``/``pmin``/``pmax`` exchange — the literal analogue of GRAPE's
  "aggregate fragmented small messages into a continuous compact buffer
  before dispatching" (the paper trades latency for throughput);
- a fragment's targets never change, so set-up groups its arcs by target
  once (a stable device sort, pad arcs last) and each superstep combines
  the messages with a dense segmented scan over those runs of equal
  target, read at each vertex's last arc: no sort and no scatter of the
  arcs per superstep, on every backend. The scan is blocked, rows of 128
  lanes then the rows' tails, because a 1-D ``lax.associative_scan`` over
  tens of millions of arcs takes minutes to compile for a TPU. The Pallas
  segment-sum behind ``use_kernels`` runs only in tests (the v5e compiler
  refuses it as written).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.storage.grin import ANALYTICS_REQUIRED, GRINAdapter
from repro.storage.partition import PAD_SENTINEL, Fragments, partition

# combiner → (operator, identity, cross-chip collective)
COMBINERS = {
    "sum": (jnp.add, 0.0, "psum"),
    "min": (jnp.minimum, jnp.inf, "pmin"),
    "max": (jnp.maximum, -jnp.inf, "pmax"),
}

LANES = 128   # the blocked scan's row width: one TPU vreg row of lanes

# the per-fragment fields, in the order the superstep passes them
EDGE_FIELDS = ("indices", "e_src", "e_mask", "weights", "run_last", "run_has")


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["indices", "e_src", "e_mask", "weights",
                                "run_last", "run_has", "owned_start",
                                "out_degree"],
                   meta_fields=["n_vertices", "v_per_frag"])
@dataclasses.dataclass
class FragmentArrays:
    """Device-resident stacked fragment arrays. A pytree: jitted fixpoints
    take it as an argument, so the edge arrays never bake into the
    compiled program as constants. Each fragment's arcs are grouped by
    target (``_group_by_target``): targets ascending, sources ascending
    within a target's run, pad arcs last."""

    indices: jnp.ndarray        # [F, E] global target ids, non-decreasing;
    #                             pad arcs (e_mask False) take the last id
    e_src: jnp.ndarray          # [F, E] local owned source index
    e_mask: jnp.ndarray         # [F, E] valid edge
    weights: Optional[jnp.ndarray]
    run_last: jnp.ndarray       # [F, N] position of each target's last arc
    #                             (0 where it has none)
    run_has: jnp.ndarray        # [F, N] the target has an arc here
    owned_start: jnp.ndarray    # [F]
    out_degree: jnp.ndarray     # [N]
    n_vertices: int
    v_per_frag: int


def _group_by_target(indices, e_src, mask, weights, n: int):
    """One fragment's arcs stably sorted by target, pad arcs last; the
    arcs arrive in (source, target) order, so sources stay ascending
    within each target's run. Also each target's last arc position and
    whether it has one."""
    key = jnp.where(mask, indices, n)            # pads sort after every id
    ops = (key, e_src) if weights is None else (key, e_src, weights)
    key, e_src, *w = jax.lax.sort(ops, num_keys=1, is_stable=True)
    pos = jnp.arange(key.shape[0], dtype=jnp.int32)
    last = jnp.full((n + 1,), -1, jnp.int32).at[key].max(pos)[:n]
    return (jnp.minimum(key, n - 1), e_src, key < n,
            w[0] if w else None, jnp.maximum(last, 0), last >= 0)


@functools.lru_cache(maxsize=8)
def _grouping(mesh, n: int) -> Callable:
    """``_group_by_target`` over every fragment as one jitted program:
    under a mesh each chip groups its own fragment in place; on one
    device a ``vmap`` over the stack. Cached, so the engines rebuilt for
    snapshots of one vertex count reuse one program; bounded, because
    each new count is a new program."""
    group = functools.partial(_group_by_target, n=n)
    if mesh is None:
        return jax.jit(jax.vmap(group))
    from jax.sharding import PartitionSpec as P

    def one(*arrs):
        out = group(*(None if a is None else a[0] for a in arrs))
        return tuple(None if a is None else a[None] for a in out)

    return jax.jit(jax.shard_map(one, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data")))


def _prepare(frags: Fragments, mesh=None) -> FragmentArrays:
    F, E = frags.indices.shape
    e_src = np.zeros((F, E), np.int32)
    for f in range(F):
        ptr = frags.indptr[f]
        e_src[f] = np.clip(
            np.searchsorted(ptr, np.arange(E), side="right") - 1,
            0, frags.v_per_frag - 1)
    mask = frags.indices != PAD_SENTINEL
    # under a mesh each chip holds its own fragment's edges; the [N]
    # degree vector is replicated
    if mesh is None:
        stacked = replicated = jnp.asarray
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def stacked(a):
            return jax.device_put(a, NamedSharding(mesh, P("data")))

        def replicated(a):
            return jax.device_put(a, NamedSharding(mesh, P()))
    edges = (np.where(mask, frags.indices, 0).astype(np.int32), e_src,
             mask, frags.weights)
    grouped = _grouping(mesh, frags.n_vertices)(
        *(None if a is None else stacked(a) for a in edges))
    return FragmentArrays(
        **dict(zip(EDGE_FIELDS, grouped)),
        owned_start=stacked(frags.owned_start),
        out_degree=replicated(frags.out_degree),
        n_vertices=frags.n_vertices,
        v_per_frag=frags.v_per_frag,
    )


def _segmented_scan(vals, heads, op, identity):
    """Inclusive scan of ``op`` along the last axis, restarting at each
    head: log2(width) shift-and-combine steps (Hillis–Steele). Returns
    the scanned values and whether a head lies at or before each
    position."""
    width = vals.shape[-1]
    lead = [(0, 0)] * (vals.ndim - 1)
    d = 1
    while d < width:
        shift = lead + [(d, 0)]
        prev_v = jnp.pad(vals[..., :-d], shift, constant_values=identity)
        prev_h = jnp.pad(heads[..., :-d], shift, constant_values=False)
        vals = jnp.where(heads, vals, op(prev_v, vals))
        heads = heads | prev_h
        d *= 2
    return vals, heads


def _combine_runs(vals, indices, run_last, run_has, op, identity):
    """Messages ``vals`` [E] in target order → the dense [N] combination
    of each target's run. Blocked: a segmented scan within rows of
    ``LANES`` arcs, then across the rows' tails, then one gather at each
    target's last arc."""
    E = vals.shape[0]
    R = -(-E // LANES)
    heads = jnp.concatenate([jnp.ones((1,), bool),
                             indices[1:] != indices[:-1]])
    v = jnp.pad(vals, (0, R * LANES - E), constant_values=identity)
    h = jnp.pad(heads, (0, R * LANES - E), constant_values=True)
    v, h = _segmented_scan(v.reshape(R, LANES), h.reshape(R, LANES), op,
                           identity)
    # the run open at each row's end, from its first arc, carried into
    # the next row's arcs before that row's first head
    tail, _ = _segmented_scan(v[:, -1], h[:, -1], op, identity)
    carry = jnp.pad(tail[:-1], (1, 0), constant_values=identity)
    v = jnp.where(h, v, op(carry[:, None], v)).reshape(-1)
    return jnp.where(run_has, v[run_last], identity)


class GrapeEngine:
    """Pregel/PIE/FLASH substrate over stacked fragments."""

    def __init__(self, store, n_frags: int = 1, mesh=None,
                 use_kernels: bool = False, reorder: bool = False):
        self.grin = GRINAdapter(store, ANALYTICS_REQUIRED)
        self.mesh = mesh
        if mesh is not None:
            n_frags = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                                   if a == "data"])) or n_frags
        self.n_frags = n_frags
        self.frags = _prepare(partition(store, n_frags, reorder=reorder),
                              mesh)
        self.use_kernels = use_kernels
        self._sharded: Dict[Tuple[str, bool], Callable] = {}

    # ------------------------------------------------------------ superstep
    def _scatter(self, fa: FragmentArrays, owned_vals: jnp.ndarray,
                 combiner: str, use_weights: bool) -> jnp.ndarray:
        """One fragment: owned vertex values → dense length-N contribution."""
        op, identity, _ = COMBINERS[combiner]
        with jax.named_scope("grape.gather"):
            vals = owned_vals[fa.e_src]                   # [E]
            if use_weights and fa.weights is not None:
                # semiring pairing: (+,×) for sum-combining flows
                # (pagerank, equity), (min,+) tropical for shortest paths
                if combiner in ("min", "max"):
                    vals = vals + fa.weights
                else:
                    vals = vals * fa.weights
            vals = jnp.where(fa.e_mask, vals, identity)
        if combiner == "sum" and self.use_kernels:
            from repro.kernels import ops as kops
            return kops.segment_sum(vals, fa.indices, fa.n_vertices)
        with jax.named_scope("grape.combine"):
            return _combine_runs(vals, fa.indices, fa.run_last, fa.run_has,
                                 op, identity)

    def _sharded_superstep(self, combiner: str, use_weights: bool):
        """The jitted shard_map superstep over the mesh's ``data`` axis,
        one per (combiner, use_weights). Jitted so eager callers (FLASH,
        PIE) run it as one program; inside a jitted fixpoint it inlines."""
        key = (combiner, use_weights)
        fn = self._sharded.get(key)
        if fn is not None:
            return fn
        from jax.sharding import PartitionSpec as P

        coll = COMBINERS[combiner][2]
        meta = self.frags

        def frag_fn(*arrs):
            *edges, vals = arrs
            local_fa = dataclasses.replace(meta, **{
                k: None if a is None else a[0]
                for k, a in zip(EDGE_FIELDS, edges)})
            contrib = self._scatter(local_fa, vals[0], combiner,
                                    use_weights)
            # the collective's result is identical on every chip
            return getattr(jax.lax, coll)(contrib, "data")

        specs = tuple(None if getattr(meta, k) is None else P("data")
                      for k in EDGE_FIELDS)
        fn = jax.jit(jax.shard_map(
            frag_fn, mesh=self.mesh, in_specs=specs + (P("data"),),
            out_specs=P()))
        self._sharded[key] = fn
        return fn

    def superstep(self, owned_vals: jnp.ndarray, combiner: str = "sum",
                  use_weights: bool = False,
                  frags: Optional[FragmentArrays] = None) -> jnp.ndarray:
        """owned_vals [F, v_per] → combined messages [N] (replicated).
        ``frags`` defaults to the engine's arrays; a jitted caller passes
        its traced copy."""
        fa = self.frags if frags is None else frags
        edges = tuple(getattr(fa, k) for k in EDGE_FIELDS)

        if self.mesh is not None:
            fn = self._sharded_superstep(combiner, use_weights)
            return fn(*edges, owned_vals)

        def frag_fn(*arrs):
            *local, vals = arrs
            return self._scatter(
                dataclasses.replace(fa, **dict(zip(EDGE_FIELDS, local))),
                vals, combiner, use_weights)

        contribs = jax.vmap(frag_fn)(*edges, owned_vals)
        if combiner == "sum":
            return jnp.sum(contribs, axis=0)
        if combiner == "min":
            return jnp.min(contribs, axis=0)
        return jnp.max(contribs, axis=0)

    # --------------------------------------------------------------- helpers
    def owned_view(self, dense: jnp.ndarray) -> jnp.ndarray:
        """[N] → [F, v_per] (pad tail with last vertex repeated)."""
        n, vp, F = self.frags.n_vertices, self.frags.v_per_frag, self.n_frags
        pad = F * vp - n
        if pad:
            dense = jnp.concatenate([dense, jnp.zeros((pad,), dense.dtype)])
        return dense.reshape(F, vp)

    def dense_view(self, owned: jnp.ndarray) -> jnp.ndarray:
        return owned.reshape(-1)[: self.frags.n_vertices]

    @property
    def out_degree(self) -> jnp.ndarray:
        return self.frags.out_degree
