"""Graph sampling for GNN training (paper §7 — GraphLearn).

Fixed-fanout k-hop neighbor sampling (GraphSAGE) and the NCN common-
neighbor sampling of the paper's §8 social-relation-prediction case. Two
backends behind one API:

- ``backend="host"`` — CPU numpy sampling, exactly the paper's decoupled
  CPU-sampling-server role; batches are dense fixed-shape arrays ready for
  the jitted trainer.
- ``backend="device"`` — the sampling hot path runs as ONE jitted device
  program on the partitioned fragment substrate the query engines use
  (``engines/sample.py``; DESIGN.md §10): per-vertex pull-ELL slabs, a
  threaded ``jax.random`` key for reproducible draws, sharded feature
  gather. ``sample_batch`` returns the same ``SampledBatch`` shapes and
  ``-1``-padding contract as the host path.

Both paths draw uniform neighbor indices by the floor-multiply map
``⌊u · deg⌋`` (``uniform_index``) instead of ``bits % deg`` — the modulo
draw is biased toward low indices whenever ``deg`` does not divide the bit
range; the floor map is exactly proportional on any equispaced grid of
uniforms (regression-tested in ``tests/test_sampler_diff.py``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.storage.grin import GRINAdapter, LEARNING_REQUIRED


def uniform_index(u: np.ndarray, degs: np.ndarray) -> np.ndarray:
    """Unbiased uniform draw: ``u ∈ [0, 1)`` → ``⌊u · deg⌋`` clipped to
    ``[0, deg)``. ``u`` and ``degs`` broadcast together."""
    d = np.asarray(degs)
    col = (u * d).astype(np.int64)
    return np.minimum(col, np.maximum(d - 1, 0))


@dataclasses.dataclass
class SampledBatch:
    """Layered GraphSAGE mini-batch: layer l has seeds^(l) and their sampled
    neighbors (fixed fanout, -1 ⇒ padded / missing)."""

    seeds: np.ndarray                   # [B] target vertices
    layers: List[np.ndarray]            # layer l: [B * prod(fanout[:l]), fanout[l]]
    features: List[np.ndarray]          # node features per layer frontier
    labels: Optional[np.ndarray] = None


class GraphSampler:
    def __init__(self, store, feature_prop: str = "feat",
                 label_prop: Optional[str] = None, seed: int = 0,
                 backend: str = "host", n_frags: int = 1,
                 use_kernels: bool = False, pg=None, typed: bool = False):
        self.grin = GRINAdapter(store, LEARNING_REQUIRED)
        self.indptr, self.indices = self.grin.adjacency()
        self.feature_prop = feature_prop
        self.label_prop = label_prop
        self._features = self.grin.vertex_prop(feature_prop)
        self._labels = (self.grin.vertex_prop(label_prop)
                        if label_prop else None)
        self.rng = np.random.default_rng(seed)
        if backend not in ("host", "device"):
            raise ValueError(f"unknown sampler backend {backend!r}")
        self.backend = backend
        self.n_frags = n_frags
        self.use_kernels = use_kernels
        # typed: the device executor draws relations (the rgcn brick)
        self.typed = typed
        self._pg = pg
        self._seed = seed
        self._device = None
        self._draws = 0
        # pipeline workers call sample_batch concurrently: the step counter
        # must be claimed atomically or two workers replay one fold_in key
        self._draws_lock = threading.Lock()
        self._base_key = None
        self._fold = None
        if backend == "device":
            self.device_executor()          # build eagerly: fail fast

    def device_executor(self):
        """The (lazily built) fragment sampling engine — shared with the
        trainer's jitted step and the ``CALL gnn.infer`` bridge."""
        if self._device is None:
            from repro.engines.sample import FragmentSampleExecutor
            self._device = FragmentSampleExecutor(
                self.grin.store, n_frags=self.n_frags,
                feature_prop=self.feature_prop, label_prop=self.label_prop,
                use_kernels=self.use_kernels, pg=self._pg, typed=self.typed)
        return self._device

    def _step_key(self, step: int):
        """``fold_in(PRNGKey(seed), step)`` without per-call eager dispatch
        (an un-jitted threefry fold costs milliseconds on CPU — more than
        the whole sampled batch)."""
        import jax

        with self._draws_lock:
            if self._base_key is None:
                self._base_key = jax.random.PRNGKey(self._seed)
                self._fold = jax.jit(jax.random.fold_in)
        return self._fold(self._base_key, np.uint32(step))

    @property
    def feature_dim(self) -> int:
        return self._features.shape[1]

    def sample_neighbors(self, nodes: np.ndarray, fanout: int) -> np.ndarray:
        """[N] → [N, fanout] sampled neighbor ids (with replacement; -1 for
        isolated vertices)."""
        starts = self.indptr[nodes]
        degs = self.indptr[nodes + 1] - starts
        with self._draws_lock:
            # np.random.Generator is not thread-safe; pipeline workers call
            # this concurrently (exp4/exp5 worker sweeps)
            u = self.rng.random((len(nodes), fanout))
        cols = uniform_index(u, np.maximum(degs, 1)[:, None])
        take = np.where(degs[:, None] > 0, starts[:, None] + cols, 0)
        out = self.indices[take].astype(np.int64)
        return np.where(degs[:, None] > 0, out, -1)

    def sample_batch(self, seeds: np.ndarray,
                     fanouts: Sequence[int]) -> SampledBatch:
        """Multi-hop sampling as a dataflow: hop l depends on hop l-1
        (the paper models exactly this dependency graph)."""
        if self.backend == "device":
            with self._draws_lock:
                step = self._draws
                self._draws += 1
            return self.sample_batch_device(seeds, fanouts,
                                            self._step_key(step))
        frontiers = [np.asarray(seeds, np.int64)]
        layers = []
        for f in fanouts:
            nbrs = self.sample_neighbors(np.maximum(frontiers[-1], 0), f)
            nbrs = np.where(frontiers[-1][:, None] >= 0, nbrs, -1)
            layers.append(nbrs)
            frontiers.append(nbrs.reshape(-1))
        feats = [self._feature_of(fr) for fr in frontiers]
        labels = None
        if self._labels is not None:
            # PAD (-1) seeds get label 0, matching the device backend's
            # zero pad row — the two backends share one batch contract
            seeds_a = np.asarray(seeds)
            labels = np.where(seeds_a >= 0,
                              self._labels[np.maximum(seeds_a, 0)], 0)
        return SampledBatch(seeds=np.asarray(seeds), layers=layers,
                            features=feats, labels=labels)

    def sample_batch_device(self, seeds: np.ndarray, fanouts: Sequence[int],
                            key) -> SampledBatch:
        """One jitted device batch under an explicit key, converted back to
        the host ``SampledBatch`` layout (the trainer's fully device-resident
        path skips this conversion — see ``SageTrainer`` backend="device")."""
        ex = self.device_executor()
        layers, feats, labels = ex.sample(seeds, key, tuple(fanouts))[:3]
        return SampledBatch(
            seeds=np.asarray(seeds),
            layers=[np.asarray(l, np.int64) for l in layers],
            features=[np.asarray(f, np.float32) for f in feats],
            labels=None if labels is None else np.asarray(labels))

    def _feature_of(self, nodes: np.ndarray) -> np.ndarray:
        safe = np.maximum(nodes, 0)
        f = self._features[safe]
        return np.where((nodes >= 0)[:, None], f, 0.0).astype(np.float32)

    # ------------------------------------------------------------------ NCN
    def sample_ncn(self, edges: np.ndarray, fanouts: Sequence[int],
                   max_common: int = 8) -> Dict[str, np.ndarray]:
        """Neural Common Neighbor sampling (paper §8, [80]): for each target
        edge (u,v), extract first-order common neighbors, then k-hop
        subgraphs around each common neighbor."""
        u, v = edges[:, 0], edges[:, 1]
        common = np.full((len(edges), max_common), -1, np.int64)
        for i, (a, b) in enumerate(zip(u, v)):
            na = self.indices[self.indptr[a]:self.indptr[a + 1]]
            nb = self.indices[self.indptr[b]:self.indptr[b + 1]]
            cn = np.intersect1d(na, nb)
            if len(cn) > max_common:
                with self._draws_lock:       # Generator is not thread-safe
                    cn = self.rng.choice(cn, max_common, replace=False)
            common[i, :len(cn)] = cn
        around = self.sample_batch(common.reshape(-1), fanouts)
        return {
            "edges": edges,
            "common": common,
            "u_batch": self.sample_batch(u, fanouts),
            "v_batch": self.sample_batch(v, fanouts),
            "cn_batch": around,
        }
