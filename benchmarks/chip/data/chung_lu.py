"""An undirected power-law graph with node features and class labels,
drawn on the device from a seed, in CSR form.

Chung-Lu model: each of ``m`` undirected edges draws both endpoints
independently, endpoint rank ``r`` with probability proportional to
``(r + 1) ** -degree_exponent`` (by the inverse of the continuous CDF),
and a seeded permutation maps ranks to vertex ids so that hubs lie
anywhere in the table. Each edge is stored in both directions, as a
loader stores an undirected graph, and the arcs are sorted by
``(source, target)``. Self-loops and repeated edges are kept. Features
are standard normal float32 rows; labels are uniform over the classes.

One jitted call draws everything; the host receives CSR arrays that the
program's immutable store adopts as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.harness import seed_key


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _draw(key, n, m, exponent, feature_dim, n_classes):
    k_perm, k_ends, k_feat, k_lab = jax.random.split(key, 4)
    a = 1.0 - exponent
    top = (n + 1.0) ** a - 1.0
    u = jax.random.uniform(k_ends, (2, m), jnp.float32)
    rank = jnp.floor((1.0 + u * top) ** (1.0 / a)).astype(jnp.int32) - 1
    ends = jax.random.permutation(k_perm, n)[jnp.clip(rank, 0, n - 1)]
    src = jnp.concatenate([ends[0], ends[1]])
    dst = jnp.concatenate([ends[1], ends[0]])
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    deg = jnp.zeros(n, jnp.int32).at[src].add(1)
    indptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(deg)])
    feat = jax.random.normal(k_feat, (n, feature_dim), jnp.float32)
    label = jax.random.randint(k_lab, (n,), 0, n_classes, jnp.int32)
    return indptr, dst, feat, label


def generate(graph: dict, seed: int) -> dict:
    """CSR arrays of one graph: ``n``, ``indptr`` (int64), ``indices``
    (int32, targets sorted within each row) and ``vprops`` (``feat``,
    ``label``)."""
    n, m = int(graph["n_nodes"]), int(graph["n_undirected_edges"])
    out = _draw(seed_key(seed), n, m, float(graph["degree_exponent"]),
                int(graph["feature_dim"]), int(graph["n_classes"]))
    indptr, indices, feat, label = jax.device_get(out)
    del out
    return {"n": n, "indptr": indptr.astype(np.int64), "indices": indices,
            "vprops": {"feat": feat, "label": label}}
