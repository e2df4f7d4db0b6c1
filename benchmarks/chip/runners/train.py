"""A closed loop of fused GraphSAGE steps on the learning engine:
``flexbuild(store, ["vineyard", "graphlearn", "sage"])``, a
``SageTrainer(..., backend="device")`` over its sampler, and
``train_step_device(step)`` (sample → gather → SGD as one device
program), each step ending when its loss reaches the host.

Set-up loads the generated CSR arrays into the program's immutable
store, builds the one trainer, gives it weights drawn from the seed and
drives it through its first steps, keeping the parameters before and
after them for the check; the window then goes on with the same object,
on the next steps' rows. A step's rows and draws depend on its index,
and the first index is drawn from the seed.

The trainer is built with one fixed seed, ``DRAW_SEED``: its step
program holds the key of its draws as a constant, so a trainer seed
that followed ``--seed`` would make every run compile the step anew.
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

# the trainer's own seed: the key of its neighbour draws
DRAW_SEED = 0


def first_step(seed: int) -> int:
    return int(np.random.default_rng([seed, 86028121]).integers(0, 2 ** 30))


@functools.partial(jax.jit, static_argnums=(1, 2))
def init_params(key, dims, n_classes):
    """GraphSAGE weights: fan-in normal weights and zero biases of
    {"l<i>": {"b", "w_nbr", "w_self"}, "out": {"b", "w"}}, one key per
    leaf in that flattened order."""
    names = []
    for i in range(len(dims) - 1):
        names += [(f"l{i}", "b", (dims[i + 1],)),
                  (f"l{i}", "w_nbr", (dims[i], dims[i + 1])),
                  (f"l{i}", "w_self", (dims[i], dims[i + 1]))]
    names += [("out", "b", (n_classes,)), ("out", "w", (dims[-1], n_classes))]
    keys = jax.random.split(key, len(names))
    params = {}
    for (layer, leaf, shape), k in zip(names, keys):
        if leaf == "b":
            v = jnp.zeros(shape, jnp.float32)
        else:
            v = (jax.random.normal(k, shape, jnp.float32)
                 * (1.0 / math.sqrt(max(1, shape[0]))))
        params.setdefault(layer, {})[leaf] = v
    return params


def host_params(trainer) -> dict:
    return jax.tree_util.tree_map(np.asarray, trainer.params)


def build_trainer(run):
    from repro.core.flexbuild import flexbuild
    from repro.learning.trainer import SageTrainer
    from repro.storage.csr import CSRStore

    ds, cfg, job = run.dataset, run.cell.config, run.cell.mix
    store = CSRStore.from_parts(ds["n"], ds["indptr"], ds["indices"],
                                vertex_props=ds["vprops"])
    run.say(f"graph: {store.n_vertices} vertices, {store.n_edges} arcs")
    dep = flexbuild(store, cfg["bricks"], **cfg.get("build", {}))
    n_classes = cfg["graph"]["n_classes"]
    trainer = SageTrainer(
        dep.engine("graphlearn"), hidden=job["hidden"], n_classes=n_classes,
        fanouts=tuple(job["fanouts"]), batch_size=job["batch_size"],
        lr=job["lr"], seed=DRAW_SEED, backend="device")
    dims = (cfg["graph"]["feature_dim"],) + (job["hidden"],) * len(
        job["fanouts"])
    params = init_params(seed_key(run.seed), dims, n_classes)
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
    run.say(f"set-up: first {job['first_steps']} steps, compile or cache "
            f"load included, {time.perf_counter() - t!r} s")
    run.program["trainer"] = trainer
    run.extra.update(step0=step0, losses=losses, snaps=snaps)


def window(run) -> None:
    trainer = run.program["trainer"]
    step = run.extra["step0"] + run.cell.mix["first_steps"]
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
    run.say(f"window: {len(losses)} steps in {run.window_s!r} s, last "
            f"loss {losses[-1]!r}")
