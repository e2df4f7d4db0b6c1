"""A heterogeneous academic graph at ogbn-mag's published counts, drawn on
the device from a seed, in the CSR form a typed store adopts.

Node types take consecutive id ranges in the order the configuration
lists them (papers first, so the papers are ids ``0 .. n_paper - 1``).
Each edge type draws its ``count`` edges Chung-Lu style: both endpoints
independently, an endpoint of type ``t`` at rank ``r`` with probability
proportional to ``(r + 1) ** -degree_exponent[t]`` (by the inverse of the
continuous CDF), one seeded permutation a type mapping ranks to ids, so a
type's hubs are the same vertices in every relation. An edge sends under
``relation`` from its source into the row of its target, and under
``reverse_relation`` the other way (an undirected type names one relation
for both). The arcs are sorted by (target row, neighbour, relation).
Self-loops and repeated edges are kept.

Features: every row of the input table is standard normal; the rows of
the featureless types are the embeddings' initial values. Labels are
uniform over the classes on the papers and 0 elsewhere.

One jitted call draws everything; the host receives arrays that the
program's immutable store adopts as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.harness import seed_key


def schema(graph: dict):
    """(node type names, counts, exponents, edge types as (source type,
    target type, count, relation, reverse relation)) in id order."""
    names = list(graph["node_types"])
    counts = tuple(int(graph["node_types"][t]) for t in names)
    expo = tuple(float(graph["degree_exponent"][t]) for t in names)
    edges = tuple((names.index(e["source"]), names.index(e["target"]),
                   int(e["count"]), int(e["relation"]),
                   int(e["reverse_relation"])) for e in graph["edge_types"])
    return names, counts, expo, edges


def n_arcs(graph: dict) -> int:
    """Every edge stored once in each direction."""
    return 2 * sum(int(e["count"]) for e in graph["edge_types"])


def _ranks(key, n, exponent, m):
    a = 1.0 - exponent
    top = (n + 1.0) ** a - 1.0
    u = jax.random.uniform(key, (m,), jnp.float32)
    rank = jnp.floor((1.0 + u * top) ** (1.0 / a)).astype(jnp.int32) - 1
    return jnp.clip(rank, 0, n - 1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _draw(key, counts, expo, edges, feature_dim, n_classes):
    n = sum(counts)
    offs = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    k_perm, k_ends, k_feat, k_lab = jax.random.split(key, 4)
    perms = [offs[t] + jax.random.permutation(k, c).astype(jnp.int32)
             for t, (k, c) in enumerate(zip(jax.random.split(
                 k_perm, len(counts)), counts))]
    rows, nbrs, rels = [], [], []
    for (s, t, m, rel, rev), k in zip(edges, jax.random.split(
            k_ends, len(edges))):
        ks, kt = jax.random.split(k)
        src = perms[s][_ranks(ks, counts[s], expo[s], m)]
        dst = perms[t][_ranks(kt, counts[t], expo[t], m)]
        rows += [dst, src]
        nbrs += [src, dst]
        rels += [jnp.full(m, rel, jnp.int32), jnp.full(m, rev, jnp.int32)]
    row, nbr, rel = jax.lax.sort(tuple(map(jnp.concatenate,
                                           (rows, nbrs, rels))), num_keys=3)
    deg = jnp.zeros(n, jnp.int32).at[row].add(1)
    indptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(deg)])
    types = jnp.repeat(jnp.arange(len(counts), dtype=jnp.int32),
                       np.asarray(counts), total_repeat_length=n)
    feat = jax.random.normal(k_feat, (n, feature_dim), jnp.float32)
    label = jnp.where(types == 0, jax.random.randint(
        k_lab, (n,), 0, n_classes, jnp.int32), 0)
    return indptr, nbr, rel, types, feat, label


def generate(graph: dict, seed: int) -> dict:
    """CSR arrays of one typed graph: ``n``, ``indptr`` (int64),
    ``indices`` (int32, each row's neighbours sorted), ``relations``
    (int32, each arc's), ``types`` (int32, each vertex's node type),
    ``counts`` (vertices a type) and ``vprops`` (``feat``, ``label``)."""
    names, counts, expo, edges = schema(graph)
    out = _draw(seed_key(seed), counts, expo, edges,
                int(graph["feature_dim"]), int(graph["n_classes"]))
    indptr, indices, relations, types, feat, label = jax.device_get(out)
    del out
    return {"n": sum(counts), "indptr": indptr.astype(np.int64),
            "indices": indices, "relations": relations, "types": types,
            "counts": dict(zip(names, counts)),
            "vprops": {"feat": feat, "label": label}}
