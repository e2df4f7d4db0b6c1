"""GNN models in pure JAX: GraphSAGE (mean aggregator), NCN link
prediction and the relational GCN — the learning-stack training backends
(paper §7/§8)."""

from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models import nn


class GraphSAGE:
    """Mean-aggregator GraphSAGE over fixed-fanout sampled batches."""

    def __init__(self, feature_dim: int, hidden: int, n_classes: int,
                 fanouts: Sequence[int]):
        self.feature_dim = feature_dim
        self.hidden = hidden
        self.n_classes = n_classes
        self.fanouts = tuple(fanouts)

    def specs(self) -> dict:
        dims = [self.feature_dim] + [self.hidden] * len(self.fanouts)
        layers = {}
        for i in range(len(self.fanouts)):
            layers[f"l{i}"] = {
                "w_self": nn.Spec((dims[i], dims[i + 1]), (None, None), "fan_in",
                                  dtype=jnp.float32),
                "w_nbr": nn.Spec((dims[i], dims[i + 1]), (None, None), "fan_in",
                                 dtype=jnp.float32),
                "b": nn.Spec((dims[i + 1],), (None,), "zeros", dtype=jnp.float32),
            }
        layers["out"] = {
            "w": nn.Spec((self.hidden, self.n_classes), (None, None), "fan_in",
                         dtype=jnp.float32),
            "b": nn.Spec((self.n_classes,), (None,), "zeros", dtype=jnp.float32),
        }
        return layers

    def init(self, key):
        return nn.init_tree(self.specs(), key, dtype=jnp.float32)

    def embed(self, params, feats: List[jnp.ndarray],
              layer_nbrs: List[jnp.ndarray]) -> jnp.ndarray:
        """feats[l]: frontier-l features [B·∏f[:l], D]; layer_nbrs[l] the
        sampled neighbor ids (only used for the valid-mask)."""
        k = len(self.fanouts)
        h = list(feats)
        for l in range(k):
            lp = params[f"l{l}"]
            new_h = []
            for depth in range(k - l):
                cur = h[depth]
                nbr = h[depth + 1].reshape(cur.shape[0], self.fanouts[depth], -1)
                valid = (layer_nbrs[depth].reshape(cur.shape[0], -1) >= 0
                         )[..., None].astype(cur.dtype)
                mean_nbr = jnp.sum(nbr * valid, axis=1) / \
                    jnp.maximum(jnp.sum(valid, axis=1), 1.0)
                z = cur @ lp["w_self"] + mean_nbr @ lp["w_nbr"] + lp["b"]
                new_h.append(jax.nn.relu(z))
            h = new_h
        return h[0]

    def logits(self, params, feats, layer_nbrs) -> jnp.ndarray:
        z = self.embed(params, feats, layer_nbrs)
        return z @ params["out"]["w"] + params["out"]["b"]

    def loss(self, params, feats, layer_nbrs, labels) -> jnp.ndarray:
        lg = self.logits(params, feats, layer_nbrs)
        return nn.softmax_cross_entropy(lg, labels)


class RGCN:
    """Relational GCN (Schlichtkrull et al. 2018) over fixed-fanout typed
    batches, as OGB's ogbn-mag baseline runs it: at each layer a target
    takes, for every relation r, the mean of its neighbours drawn under r
    times W_r (no bias), plus its own row times the root weight of its
    node type, with that type's bias; ReLU between layers, the last layer
    gives the logits."""

    def __init__(self, feature_dim: int, hidden: int, n_classes: int,
                 fanouts: Sequence[int], n_relations: int, n_types: int):
        self.fanouts = tuple(fanouts)
        self.dims = ((feature_dim,) + (hidden,) * (len(self.fanouts) - 1)
                     + (n_classes,))
        self.n_relations = n_relations
        self.n_types = n_types

    def specs(self) -> dict:
        R, T = self.n_relations, self.n_types
        layers = {}
        for i, (d_in, d_out) in enumerate(zip(self.dims, self.dims[1:])):
            std = 1.0 / math.sqrt(d_in)
            layers[f"l{i}"] = {
                "w_rel": nn.Spec((R, d_in, d_out), (None,) * 3, "normal",
                                 scale=std, dtype=jnp.float32),
                "w_root": nn.Spec((T, d_in, d_out), (None,) * 3, "normal",
                                  scale=std, dtype=jnp.float32),
                "b": nn.Spec((T, d_out), (None, None), "zeros",
                             dtype=jnp.float32),
            }
        return layers

    def init(self, key):
        return nn.init_tree(self.specs(), key, dtype=jnp.float32)

    def conv(self, p, cur, nbr, rel, types) -> jnp.ndarray:
        """One layer for targets ``cur`` [M, D] of node ``types`` [M]:
        ``nbr`` [M, F, D] their draws, ``rel`` [M, F] each draw's relation
        (< 0 for padding, which no mean counts)."""
        hot = (rel[..., None] == jnp.arange(self.n_relations)).astype(
            cur.dtype)                                          # [M, F, R]
        total = jnp.einsum("mfr,mfd->mrd", hot, nbr)
        mean = total / jnp.maximum(jnp.sum(hot, axis=1), 1.0)[..., None]
        own = jax.nn.one_hot(types, self.n_types, dtype=cur.dtype)
        return (jnp.einsum("mrd,rde->me", mean, p["w_rel"])
                + jnp.einsum("mt,md,tde->me", own, cur, p["w_root"])
                + own @ p["b"])

    def logits(self, params, feats: List[jnp.ndarray],
               rels: List[jnp.ndarray],
               types: List[jnp.ndarray]) -> jnp.ndarray:
        """feats[d] and types[d]: the rows and node types of frontier d,
        whose rows at depth d + 1 are the draws of depth d; rels[d]:
        [rows(d), fanouts[d]] the relation of each draw, < 0 where the
        draw is padding."""
        k = len(self.fanouts)
        h = list(feats)
        for l in range(k):
            p = params[f"l{l}"]
            h = [self.conv(p, h[d], h[d + 1].reshape(
                h[d].shape[0], self.fanouts[d], -1),
                rels[d].reshape(h[d].shape[0], -1), types[d])
                for d in range(k - l)]
            if l < k - 1:
                h = [jax.nn.relu(x) for x in h]
        return h[0]

    def loss(self, params, feats, rels, types, labels) -> jnp.ndarray:
        lg = self.logits(params, feats, rels, types)
        return nn.softmax_cross_entropy(lg, labels)


class NCN:
    """Neural Common Neighbor link predictor [80]: scores an edge (u,v) from
    the pooled GraphSAGE embeddings of u, v and their common neighbors."""

    def __init__(self, feature_dim: int, hidden: int, fanouts: Sequence[int]):
        self.backbone = GraphSAGE(feature_dim, hidden, hidden, fanouts)
        self.hidden = hidden

    def specs(self):
        return {
            "backbone": self.backbone.specs(),
            "edge_mlp": {
                "w1": nn.Spec((3 * self.hidden, self.hidden), (None, None),
                              "fan_in", dtype=jnp.float32),
                "b1": nn.Spec((self.hidden,), (None,), "zeros", dtype=jnp.float32),
                "w2": nn.Spec((self.hidden, 1), (None, None), "fan_in",
                              dtype=jnp.float32),
            },
        }

    def init(self, key):
        return nn.init_tree(self.specs(), key, dtype=jnp.float32)

    def score(self, params, batch) -> jnp.ndarray:
        bp = params["backbone"]
        eu = self.backbone.logits(bp, batch["u_feats"], batch["u_nbrs"])
        ev = self.backbone.logits(bp, batch["v_feats"], batch["v_nbrs"])
        ecn = self.backbone.logits(bp, batch["cn_feats"], batch["cn_nbrs"])
        B = eu.shape[0]
        ecn = ecn.reshape(B, -1, self.hidden)
        cn_mask = (batch["common"] >= 0)[..., None].astype(eu.dtype)
        pooled = jnp.sum(ecn * cn_mask, axis=1) / \
            jnp.maximum(jnp.sum(cn_mask, axis=1), 1.0)
        z = jnp.concatenate([eu, ev, pooled], axis=-1)
        m = params["edge_mlp"]
        z = jax.nn.relu(z @ m["w1"] + m["b1"])
        return (z @ m["w2"])[:, 0]

    def loss(self, params, batch, labels) -> jnp.ndarray:
        s = self.score(params, batch)
        return jnp.mean(
            jnp.maximum(s, 0) - s * labels + jnp.log1p(jnp.exp(-jnp.abs(s))))
