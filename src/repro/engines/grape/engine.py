"""GRAPE — distributed analytical engine (paper §6), TPU-idiomatic.

Fragment execution follows the paper's design translated to JAX:

- fragments are stacked dense arrays ``[F, ...]`` (partition.py) distributed
  with ``shard_map`` over the ``data`` mesh axis (or ``vmap`` on one device);
- per superstep each fragment scatters its out-edge contributions into ONE
  dense length-N message buffer, combined locally (``segment-sum`` combiner)
  BEFORE a single ``psum``/``pmin``/``pmax`` exchange — the literal analogue
  of GRAPE's "aggregate fragmented small messages into a continuous compact
  buffer before dispatching" (the paper trades latency for throughput);
- the scatter-add hot loop is a jnp scatter on every backend; the Pallas
  segment-sum behind ``use_kernels`` runs only in tests (the v5e
  compiler refuses it as written).
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

COMBINERS = {
    "sum": (jnp.zeros, lambda buf, idx, val: buf.at[idx].add(val), "psum"),
    "min": (lambda shape, dt: jnp.full(shape, jnp.inf, dt),
            lambda buf, idx, val: buf.at[idx].min(val), "pmin"),
    "max": (lambda shape, dt: jnp.full(shape, -jnp.inf, dt),
            lambda buf, idx, val: buf.at[idx].max(val), "pmax"),
}


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["indices", "e_src", "e_mask", "weights",
                                "owned_start", "out_degree"],
                   meta_fields=["n_vertices", "v_per_frag"])
@dataclasses.dataclass
class FragmentArrays:
    """Device-resident stacked fragment arrays. A pytree: jitted fixpoints
    take it as an argument, so the edge arrays never bake into the
    compiled program as constants."""

    indices: jnp.ndarray        # [F, E] global neighbor ids; PAD_SENTINEL
    #                             entries are rebased to 0 with e_mask False
    #                             (scatter-safe: vertex 0 contributions are
    #                             zeroed by the mask, never by the id)
    e_src: jnp.ndarray          # [F, E] local owned source index
    e_mask: jnp.ndarray         # [F, E] valid edge
    weights: Optional[jnp.ndarray]
    owned_start: jnp.ndarray    # [F]
    out_degree: jnp.ndarray     # [N]
    n_vertices: int
    v_per_frag: int


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
    return FragmentArrays(
        indices=stacked(np.where(mask, frags.indices, 0)),
        e_src=stacked(e_src),
        e_mask=stacked(mask),
        weights=None if frags.weights is None else stacked(frags.weights),
        owned_start=stacked(frags.owned_start),
        out_degree=replicated(frags.out_degree),
        n_vertices=frags.n_vertices,
        v_per_frag=frags.v_per_frag,
    )


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
        init, scat, _ = COMBINERS[combiner]
        vals = owned_vals[fa.e_src]                       # [E]
        if use_weights and fa.weights is not None:
            # semiring pairing: (+,×) for sum-combining flows (pagerank,
            # equity), (min,+) tropical for shortest paths
            if combiner in ("min", "max"):
                vals = vals + fa.weights
            else:
                vals = vals * fa.weights
        if combiner == "sum":
            vals = jnp.where(fa.e_mask, vals, 0.0)
            if self.use_kernels:
                from repro.kernels import ops as kops
                return kops.segment_sum(vals, fa.indices, fa.n_vertices)
            buf = jnp.zeros((fa.n_vertices,), vals.dtype)
            return buf.at[fa.indices].add(vals)
        pad = jnp.inf if combiner == "min" else -jnp.inf
        vals = jnp.where(fa.e_mask, vals, pad)
        buf = init((fa.n_vertices,), vals.dtype)
        return scat(buf, fa.indices, vals)

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

        def frag_fn(idx, esrc, emask, w, vals):
            local_fa = dataclasses.replace(
                meta, indices=idx[0], e_src=esrc[0], e_mask=emask[0],
                weights=None if w is None else w[0])
            contrib = self._scatter(local_fa, vals[0], combiner,
                                    use_weights)
            # the collective's result is identical on every chip
            return getattr(jax.lax, coll)(contrib, "data")

        w_spec = None if meta.weights is None else P("data")
        fn = jax.jit(jax.shard_map(
            frag_fn, mesh=self.mesh,
            in_specs=(P("data"), P("data"), P("data"), w_spec, P("data")),
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

        if self.mesh is not None:
            fn = self._sharded_superstep(combiner, use_weights)
            return fn(fa.indices, fa.e_src, fa.e_mask, fa.weights,
                      owned_vals)

        contribs = jax.vmap(
            lambda i, s, m, w, v: self._scatter(
                dataclasses.replace(fa, indices=i, e_src=s, e_mask=m,
                                    weights=w),
                v, combiner, use_weights),
            in_axes=(0, 0, 0, None if fa.weights is None else 0, 0),
        )(fa.indices, fa.e_src, fa.e_mask, fa.weights, owned_vals)
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
