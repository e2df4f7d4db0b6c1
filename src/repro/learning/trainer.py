"""GNN trainers gluing sampler → pipeline → jitted update (paper §7):
``SageTrainer`` (GraphSAGE) and ``RGCNTrainer`` (the relational GCN on a
typed store, device backend only).

Two backends:

- ``backend="numpy"`` — the paper's decoupled architecture: CPU sampler
  workers produce host batches, the jitted update consumes them (optionally
  through :class:`DecoupledPipeline`, with device prefetch).
- ``backend="device"`` — sample → gather → SGD is ONE jitted device program
  per step on the fragment substrate (``engines/sample.py``): no host numpy
  round-trip per layer, draws keyed by ``fold_in(base_key, step)``.

Trained models serve from queries through the procedure bridge:
``register_inference`` freezes the current parameters into a
``CALL gnn.infer($model)`` procedure (DESIGN.md §10) whose full-graph
forward pass is deterministic under a fixed key — so serving scores equal
the offline ``infer_scores`` of the same snapshot bit-for-bit.
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.learning.gnn import RGCN, GraphSAGE
from repro.learning.pipeline import DecoupledPipeline
from repro.learning.sampler import GraphSampler


def _on_table_device(fn, ex, donate=()):
    """``jax.jit(fn)`` with every argument on the device of ``ex``'s
    feature table. A table placed in its own layout is committed
    (``engines/sample.py`` ``resident_table``), so the program's outputs
    are too: without a fixed placement, parameters fresh from ``init``
    (uncommitted) and the ones a step returns would lower to two programs,
    and the first step would compile or load a program of its own."""
    return jax.jit(fn, in_shardings=ex.feats.sharding,
                   donate_argnums=donate)


class SageTrainer:
    def __init__(self, sampler: GraphSampler, hidden: int, n_classes: int,
                 fanouts: Sequence[int], batch_size: int = 256,
                 lr: float = 1e-2, seed: int = 0, backend: str = "numpy"):
        self.sampler = sampler
        self.model = GraphSAGE(sampler.feature_dim, hidden, n_classes, fanouts)
        self.fanouts = tuple(fanouts)
        self.batch_size = batch_size
        self.lr = lr
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.rng = np.random.default_rng(seed)
        if backend not in ("numpy", "device"):
            raise ValueError(f"unknown trainer backend {backend!r}")
        self.backend = backend
        self._base_key = jax.random.PRNGKey(seed)
        self._update = jax.jit(self._update_fn)
        self._executor = None
        self._device_step = None
        self._infer_runners: Dict[int, Tuple] = {}
        # foreign-snapshot executors each pin a device copy of the feature
        # matrix + sampling slab; LRU-bounded so a stream of MVCC snapshots
        # served through gnn.infer cannot grow memory without bound
        self._ext_executors: "OrderedDict[int, Tuple]" = OrderedDict()
        self.max_ext_executors = 4
        if backend == "device":
            self._executor = sampler.device_executor()
            if sampler.label_prop is None:
                raise ValueError("backend='device' training needs the "
                                 "sampler's label_prop")
            self._device_step = _on_table_device(self._device_step_fn,
                                                 self._executor)
            self._seed_range = (0, self._executor.n_vertices)

    def sample(self, step: int) -> Dict[str, np.ndarray]:
        n = self.sampler.grin.n_vertices
        rng = np.random.default_rng(step)
        seeds = rng.integers(0, n, self.batch_size)
        b = self.sampler.sample_batch(seeds, self.fanouts)
        return {
            "feats": b.features,
            "nbrs": b.layers,
            "labels": b.labels.astype(np.int32),
        }

    def _sgd(self, params, loss, *inputs):
        """One SGD step on ``loss(params, *inputs)`` → (params, loss,
        the gradients of ``inputs``), under the device scopes
        ``model.fwd_bwd`` (the backward ops carry it too) and
        ``model.update``."""
        with jax.named_scope("model.fwd_bwd"):
            l, (g, *g_inputs) = jax.value_and_grad(
                loss, argnums=tuple(range(len(inputs) + 1)))(params, *inputs)
        with jax.named_scope("model.update"):
            params = jax.tree_util.tree_map(lambda p, gg: p - self.lr * gg,
                                            params, g)
        return params, l, g_inputs

    def _update_fn(self, params, feats, nbrs, labels):
        return self._sgd(params, lambda p: self.model.loss(p, feats, nbrs,
                                                            labels))[:2]

    def train_on(self, batch) -> float:
        self.params, l = self._update(self.params, batch["feats"],
                                      batch["nbrs"], batch["labels"])
        return float(l)

    # -------------------------------------------------- device-resident path
    def _device_step_fn(self, params, tables, step, seeds):
        """sample → gather → SGD as one traced program (DESIGN.md §10).
        The per-step key folds INSIDE the jit — an eager fold_in costs more
        than the whole sampled batch on CPU. The device tables are an
        argument: closed over, the feature matrix would bake into the
        program as a constant."""
        key = jax.random.fold_in(self._base_key, step)
        layers, feats, labels, _ = self._executor._sample_impl(
            tables, seeds, key, self.fanouts)
        return self._sgd(params, lambda p: self.model.loss(p, feats, layers,
                                                            labels))[:2]

    def _dispatch(self, step, seeds):
        """Launch the jitted step; what ``_wait`` reads its loss from."""
        self.params, l = self._device_step(self.params,
                                           self._executor._tables, step,
                                           seeds)
        return l

    def _wait(self, out) -> float:
        return float(out)

    def train_step_device(self, step: int) -> float:
        """One fused step; its loss on the host. Host spans (DESIGN.md
        §10): ``flex.learning.step`` around the call, and inside it the
        seed draw, the dispatch of the jitted step (a compile or a cache
        load lands there) and the wait for the loss."""
        with StepTraceAnnotation("flex.learning.step", step_num=step):
            with TraceAnnotation("flex.learning.seeds"):
                # same per-step seed schedule as the numpy path's ``sample``
                rng = np.random.default_rng(step)
                seeds = rng.integers(*self._seed_range,
                                     self.batch_size).astype(np.int32)
            with TraceAnnotation("flex.learning.dispatch"):
                out = self._dispatch(np.uint32(step), seeds)
            with TraceAnnotation("flex.learning.loss_wait"):
                return self._wait(out)

    def train(self, steps: int, pipelined: bool = True,
              n_workers: int = 2, prefetch: str = "host"
              ) -> Tuple[float, list]:
        losses = []
        if self.backend == "device":
            # sampling lives inside the jitted step; nothing to pipeline
            for step in range(steps):
                losses.append(self.train_step_device(step))
        elif pipelined:
            pipe = DecoupledPipeline(self.sample, n_workers=n_workers,
                                     prefetch=prefetch)
            try:
                for _ in range(steps):
                    _, batch = pipe.get()
                    losses.append(self.train_on(batch))
            finally:
                pipe.close()
        else:
            for step in range(steps):
                losses.append(self.train_on(self.sample(step)))
        return losses[-1], losses

    # ------------------------------------------------- query-serving bridge
    def _executor_for(self, store):
        """A sampling executor over ``store`` (the trainer's own store reuses
        its engine; foreign snapshots get one each, LRU-cached by identity up
        to ``max_ext_executors``)."""
        if store is None or store is self.sampler.grin.store:
            return self.sampler.device_executor()
        cached = self._ext_executors.get(id(store))
        if cached is not None and cached[0] is store:
            self._ext_executors.move_to_end(id(store))
            return cached[1]
        from repro.engines.sample import FragmentSampleExecutor
        ex = FragmentSampleExecutor(
            store, n_frags=self.sampler.n_frags,
            feature_prop=self.sampler.feature_prop, label_prop=None,
            use_kernels=self.sampler.use_kernels)
        self._ext_executors[id(store)] = (store, ex)
        while len(self._ext_executors) > self.max_ext_executors:
            _, (_, old_ex) = self._ext_executors.popitem(last=False)
            self._infer_runners.pop(id(old_ex), None)
        return ex

    def _infer_runner(self, ex):
        cached = self._infer_runners.get(id(ex))
        if cached is not None and cached[0] is ex:
            return cached[1]

        def score(params, tables, base_key, i, seeds):
            key = jax.random.fold_in(base_key, i)
            layers, feats, _, _ = ex._sample_impl(tables, seeds, key,
                                                  self.fanouts)
            lg = self.model.logits(params, feats, layers)
            return jnp.max(lg, axis=-1)          # max-logit confidence

        fn = _on_table_device(score, ex)
        self._infer_runners[id(ex)] = (ex, fn)
        return fn

    # the fixed serving chunk: draws fold per chunk index, so the grid must
    # never move or offline scores would diverge from served ones
    INFER_CHUNK = 2048

    def infer_scores(self, store=None, params=None,
                     key: int = 0) -> np.ndarray:
        """Deterministic full-graph forward pass: per-vertex max-logit score
        [N], neighbor draws keyed by ``fold_in(PRNGKey(key), chunk_index)``
        on the fixed ``INFER_CHUNK`` grid — the exact computation
        ``CALL gnn.infer`` serves, bit for bit."""
        params = self.params if params is None else params
        ex = self._executor_for(store)
        n = ex.n_vertices
        chunk = self.INFER_CHUNK
        fn = self._infer_runner(ex)
        base = jax.random.PRNGKey(key)
        out = np.empty(n, np.float32)
        for i, lo in enumerate(range(0, n, chunk)):
            hi = min(lo + chunk, n)
            seeds = np.full(chunk, -1, np.int32)
            seeds[:hi - lo] = np.arange(lo, hi)
            s = fn(params, ex._tables, base, np.uint32(i), seeds)
            out[lo:hi] = np.asarray(s)[:hi - lo]
        return out

    def as_procedure(self, key: int = 0):
        """Freeze the CURRENT parameters into a ``(store) → scores[N]``
        serving function. Later training steps do NOT change an
        already-created procedure — re-register to serve new parameters
        (lifetime rules: DESIGN.md §10)."""
        params = self.params

        def infer_fn(store):
            return self.infer_scores(store=store, params=params, key=key)

        return infer_fn

    def register_inference(self, registry, name: str = "default",
                           key: int = 0) -> str:
        """Register this model in a :class:`ProcedureRegistry` so queries
        serve it: ``CALL gnn.infer($model) YIELD v, score``."""
        registry.register_model(name, self.as_procedure(key))
        return name


class RGCNTrainer(SageTrainer):
    """Relational GCN training on a typed store (``flexbuild`` brick
    ``rgcn``; DESIGN.md §10), on the device backend: one jitted program a
    step samples typed draws from seeds of one node type, gathers their
    input rows, takes the R-GCN's loss and SGD, and updates the input
    table's learned rows sparsely.

    The input table is the executor's feature table: rows of the node
    types in ``embed_types`` are trainable embeddings, the others fixed
    features. The gradient is taken with respect to the gathered rows,
    summed over each learned row's draws, and ``-lr·sum`` is
    scatter-added into the table, which the step takes donated and hands
    back; no other row is written.
    ``embed_rows`` counts the distinct learned rows the steps updated."""

    def __init__(self, sampler, hidden: int, n_classes: int,
                 fanouts: Sequence[int], seed_type: int,
                 embed_types: Sequence[int], batch_size: int = 256,
                 lr: float = 1e-2, seed: int = 0):
        ex = sampler.device_executor()
        if not ex.n_relations:
            raise ValueError("R-GCN training needs a typed sampler: build "
                             "with the 'rgcn' brick")
        if sampler.label_prop is None:
            raise ValueError("R-GCN training needs the sampler's label_prop")
        types = np.asarray(sampler.grin.vertex_labels())
        ids = np.flatnonzero(types == seed_type)
        if len(ids) == 0 or ids[-1] - ids[0] + 1 != len(ids):
            raise ValueError(f"the vertices of seed type {seed_type} are "
                             "not one contiguous id range")
        n_types = int(types.max()) + 1
        learned = np.zeros(n_types, bool)
        learned[list(embed_types)] = True
        self.sampler = sampler
        self.model = RGCN(sampler.feature_dim, hidden, n_classes, fanouts,
                          ex.n_relations, n_types)
        self.fanouts = tuple(fanouts)
        self.batch_size = batch_size
        self.lr = lr
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.backend = "device"
        self._base_key = jax.random.PRNGKey(seed)
        self._executor = ex
        self._seed_range = (int(ids[0]), int(ids[-1]) + 1)
        self._learned = learned
        self.embed_rows = 0
        self._device_step = _on_table_device(self._device_step_fn, ex,
                                             donate=(1,))

    def _embed_update(self, table, frontiers, types, grads):
        """``table`` with ``-lr·grads`` added to the rows of the learned
        types among ``frontiers``, and the number of distinct rows so
        written. The draws of one row first sum their gradients, so each
        row moves once, by the dense gradient's step: added one by one,
        updates under half a unit in the last place of a row's value
        would each round away. Every other row's index, and a padded
        entry's, is out of range, so the scatter drops it."""
        n = table.shape[0]
        learned = jnp.asarray(self._learned)
        ids = jnp.concatenate([jnp.where(learned[t] & (fr >= 0), fr, n)
                               for fr, t in zip(frontiers, types)])
        grads = jnp.concatenate(grads)
        with jax.named_scope("embed.update"):
            order = jnp.argsort(ids)
            ids = ids[order]
            head = jnp.concatenate([jnp.ones(1, bool), ids[1:] != ids[:-1]])
            seg = jnp.cumsum(head) - 1
            total = jax.ops.segment_sum(grads[order], seg, ids.shape[0],
                                        indices_are_sorted=True)
            # each segment's row; past the last segment, distinct rows
            # beyond the table, so the indices stay sorted and unique
            rows = (n + 1 + jnp.arange(ids.shape[0])).at[seg].set(ids)
            table = table.at[rows].add(-self.lr * total, mode="drop",
                                       indices_are_sorted=True,
                                       unique_indices=True)
            count = jnp.sum(head & (ids < n), dtype=jnp.int32)
        return table, count

    def _device_step_fn(self, params, table, tables, step, seeds):
        """sample → gather → R-GCN loss and SGD → sparse row update, one
        traced program; ``table`` is the donated input table (``tables``
        holds no ``feats``)."""
        key = jax.random.fold_in(self._base_key, step)
        layers, feats, labels, (rels, types) = self._executor._sample_impl(
            {**tables, "feats": table}, seeds, key, self.fanouts)
        params, l, (g_rows,) = self._sgd(
            params, lambda p, x: self.model.loss(p, x, rels, types, labels),
            feats)
        frontiers = [seeds] + [nb.reshape(-1) for nb in layers]
        table, rows = self._embed_update(table, frontiers, types, g_rows)
        return params, table, l, rows

    def _dispatch(self, step, seeds):
        ex = self._executor
        self.params, table, l, rows = self._device_step(
            self.params, ex.feats, {**ex._tables, "feats": None}, step,
            seeds)
        ex.feats = table
        ex._tables = {**ex._tables, "feats": table}
        return l, rows

    def _wait(self, out) -> float:
        l, rows = jax.device_get(out)
        self.embed_rows += int(rows)
        return float(l)

    def _infer_runner(self, ex):
        raise NotImplementedError("gnn.infer serves GraphSAGE models only")
