"""A closed loop of fused R-GCN steps on the learning engine:
``flexbuild(store, ["vineyard", "graphlearn", "rgcn"])`` over the typed
store (node types as vertex labels, relations as edge labels), an
``RGCNTrainer`` over its sampler, and ``train_step_device(step)`` (typed
sample → gather → R-GCN SGD → sparse embedding update as one device
program), each step ending when its loss reaches the host.

Set-up loads the generated arrays into the program's immutable store,
builds the one trainer, gives it weights drawn from the seed and drives
it through its first steps, keeping the weights before, after the first
and after the last of them, and the input table after the last, for the
check; the window then goes on with the same object. The trainer's draw
seed is fixed, as in ``runners/train.py``. The window sums each step's
count of distinct embedding rows updated as ``extra["embed_rows"]``.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks.chip.harness import seed_key
from benchmarks.chip.runners.train import DRAW_SEED, first_step, host_params


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def init_params(key, dims, n_relations, n_types):
    """R-GCN weights {"l<i>": {"b", "w_rel", "w_root"}}: zero biases
    [types, d_out], and standard normal weights over sqrt(d_in) of
    [relations, d_in, d_out] and [types, d_in, d_out], a key each."""
    keys = jax.random.split(key, 2 * (len(dims) - 1))
    params = {}
    for i, (d_in, d_out) in enumerate(zip(dims, dims[1:])):
        def normal(k, lead):
            return jax.random.normal(k, (lead, d_in, d_out),
                                     jnp.float32) / math.sqrt(d_in)
        params[f"l{i}"] = {"b": jnp.zeros((n_types, d_out), jnp.float32),
                           "w_rel": normal(keys[2 * i], n_relations),
                           "w_root": normal(keys[2 * i + 1], n_types)}
    return params


def type_ids(cfg: dict):
    """(seed type id, embedding type ids) by the order of the node types."""
    names = list(cfg["graph"]["node_types"])
    learn = cfg["learn"]
    return (names.index(learn["seed_type"]),
            [names.index(t) for t in learn["embed_types"]])


def model_dims(cfg: dict, job: dict):
    return ((cfg["graph"]["feature_dim"],)
            + (job["hidden"],) * (len(job["fanouts"]) - 1)
            + (cfg["graph"]["n_classes"],))


def n_relations(cfg: dict) -> int:
    return 1 + max(max(e["relation"], e["reverse_relation"])
                   for e in cfg["graph"]["edge_types"])


def build_trainer(run):
    from repro.core.flexbuild import flexbuild
    from repro.learning.trainer import RGCNTrainer
    from repro.storage.csr import CSRStore

    ds, cfg, job = run.dataset, run.cell.config, run.cell.mix
    store = CSRStore.from_parts(ds["n"], ds["indptr"], ds["indices"],
                                vertex_props=ds["vprops"],
                                vertex_labels=ds["types"],
                                edge_labels=ds["relations"])
    run.say(f"graph: {store.n_vertices} vertices, {store.n_edges} arcs")
    dep = flexbuild(store, cfg["bricks"], **cfg.get("build", {}))
    seed_type, embed_types = type_ids(cfg)
    trainer = RGCNTrainer(
        dep.engine("graphlearn"), hidden=job["hidden"],
        n_classes=cfg["graph"]["n_classes"], fanouts=tuple(job["fanouts"]),
        seed_type=seed_type, embed_types=embed_types,
        batch_size=job["batch_size"], lr=job["lr"], seed=DRAW_SEED)
    params = init_params(seed_key(run.seed), model_dims(cfg, job),
                         n_relations(cfg), len(cfg["graph"]["node_types"]))
    same = jax.tree_util.tree_map(lambda a, b: a.shape == b.shape, params,
                                  trainer.params)
    assert all(jax.tree_util.tree_leaves(same)), "weights do not fit"
    trainer.params = params
    return trainer


def setup(run) -> None:
    job = run.cell.mix
    t = time.perf_counter()
    trainer = build_trainer(run)
    run.say(f"set-up: store, deployment and trainer "
            f"{time.perf_counter() - t!r} s")
    t = time.perf_counter()
    step0 = first_step(run.seed)
    snaps = {0: host_params(trainer)}
    losses = []
    for i in range(job["first_steps"]):
        losses.append(trainer.train_step_device(step0 + i))
        if i == 0:
            snaps[1] = host_params(trainer)
    snaps[job["first_steps"]] = host_params(trainer)
    table = np.asarray(trainer._executor.feats)
    run.say(f"set-up: first {job['first_steps']} steps, compile or cache "
            f"load included, {time.perf_counter() - t!r} s")
    run.program["trainer"] = trainer
    run.extra.update(step0=step0, losses=losses, snaps=snaps, table=table)


def window(run) -> None:
    trainer = run.program["trainer"]
    step = run.extra["step0"] + run.cell.mix["first_steps"]
    rows0 = trainer.embed_rows
    losses = []
    t_open = time.perf_counter()
    deadline = t_open + run.seconds
    while True:
        with TraceAnnotation("bench.step"):
            losses.append(trainer.train_step_device(step))
        step += 1
        now = time.perf_counter()
        if now >= deadline:
            break
    run.window_s = now - t_open
    run.attempted = len(losses)
    run.failed = sum(not math.isfinite(x) for x in losses)
    run.extra["window_steps"] = len(losses)
    run.extra["embed_rows"] = trainer.embed_rows - rows0
    run.say(f"window: {len(losses)} steps in {run.window_s!r} s, last "
            f"loss {losses[-1]!r}, {run.extra['embed_rows']} embedding "
            f"rows updated")
