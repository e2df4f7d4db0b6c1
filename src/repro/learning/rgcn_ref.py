"""The plain reference of one R-GCN training step (``learning/gnn.py``
``RGCN``, ``learning/trainer.py`` ``RGCNTrainer``), for the tests to
compare the program with.

Straightforward float32 ``jax.numpy`` at ``highest`` matmul precision,
with no sampler executor and no kernel. It takes one sampled batch —
every frontier's vertex ids (< 0 for padding), each draw's relation, each
frontier's node types and the seeds' labels — and the whole input table,
and gives the logits, the mean softmax cross-entropy, and the gradients
of the weights and of the whole table by dense autodiff; ``sgd_step``
applies them, moving only the table's learned rows.

Departures from OGB's ogbn-mag R-GCN example
(``examples/nodeproppred/mag/sampler.py`` in snap-stanford/ogb), all
shared with the program:

- neighbours are drawn with replacement, a fixed fanout per target, as
  the repository's sampler draws them (the example's sampler draws
  without replacement, up to the fanout);
- no dropout (the example drops half of the hidden units in training);
- plain SGD (the example uses Adam at lr 0.01).
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp


def _rows(table, ids):
    """The table's rows of ``ids``, zero where an id is padding."""
    x = table[jnp.maximum(ids, 0)]
    return jnp.where((ids >= 0)[:, None], x, 0.0)


def _conv(p, cur, nbr, rel, types):
    """One layer: per relation the mean of its draws times W_r, plus the
    root weight and bias of each target's node type."""
    out = jnp.zeros((cur.shape[0], p["w_rel"].shape[-1]), cur.dtype)
    for r in range(p["w_rel"].shape[0]):
        hit = (rel == r).astype(cur.dtype)                     # [M, F]
        total = jnp.sum(nbr * hit[..., None], axis=1)
        mean = total / jnp.maximum(jnp.sum(hit, axis=1), 1.0)[:, None]
        out = out + mean @ p["w_rel"][r]
    for t in range(p["w_root"].shape[0]):
        own = cur @ p["w_root"][t] + p["b"][t]
        out = out + jnp.where((types == t)[:, None], own, 0.0)
    return out


def logits(params, table, frontiers: List[jnp.ndarray],
           rels: List[jnp.ndarray], types: List[jnp.ndarray],
           fanouts: Sequence[int]) -> jnp.ndarray:
    """frontiers[d]: the ids of depth d, ``len(fanouts) + 1`` of them,
    each entry of depth d + 1 one draw of depth d; rels[d] [rows(d),
    fanouts[d]]; types[d] [rows(d)]."""
    k = len(fanouts)
    h = [_rows(table, f) for f in frontiers]
    for l in range(k):
        p = params[f"l{l}"]
        h = [_conv(p, h[d], h[d + 1].reshape(h[d].shape[0], fanouts[d], -1),
                   rels[d].reshape(h[d].shape[0], -1), types[d])
             for d in range(k - l)]
        if l < k - 1:
            h = [jax.nn.relu(x) for x in h]
    return h[0]


def loss(params, table, frontiers, rels, types, labels, fanouts):
    lg = logits(params, table, frontiers, rels, types, fanouts)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=1)[:, 0]
    return jnp.mean(jax.scipy.special.logsumexp(lg, axis=-1) - gold)


def grads(params, table, frontiers, rels, types, labels, fanouts):
    """(loss, gradient of the weights, dense gradient of the table)."""
    with jax.default_matmul_precision("highest"):
        l, (g, g_table) = jax.value_and_grad(loss, argnums=(0, 1))(
            params, table, frontiers, rels, types, labels, tuple(fanouts))
    return l, g, g_table


def sgd_step(params, table, row_learned, frontiers, rels, types, labels,
             fanouts, lr):
    """(loss, weights, table) after one SGD step: every weight moves by
    ``-lr·grad``, and of the table only the rows ``row_learned`` marks."""
    l, g, g_table = grads(params, table, frontiers, rels, types, labels,
                          fanouts)
    params = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)
    table = table - lr * jnp.where(row_learned[:, None], g_table, 0.0)
    return l, params, table
