"""A closed loop of Graphalytics kernels on the GRAPE engine of a
deployment sharded over the cell's chips:
``flexbuild(store, ["grape"], mesh=Mesh(chips, ("data",)))`` cuts the
generated graph into one fragment per chip, and each kernel of the
rotation ``bfs(s)``, ``pagerank(damping, 10 steps, tol 0)``, ``wcc()``
runs as one jitted Pregel fixpoint over them. A run ends when its answer
is on the host.

``s`` is the vertex of highest degree (Graphalytics fixes one BFS source
a dataset); it enters only the fixpoint's first state, so every seed
runs the same programs. Set-up builds the deployment and runs one whole
rotation, which compiles the three fixpoints or loads them from the
cache; the window runs whole rotations until its seconds have passed,
and keeps every answer for the check.
"""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

ALGOS = ("bfs", "pagerank", "wcc")


def build_engine(run):
    from repro.core.flexbuild import flexbuild
    from repro.storage.csr import CSRStore

    ds, cfg = run.dataset, run.cell.config
    if cfg["graph"]["fragments"] != run.cell.chips:
        raise ValueError(f"{cfg['graph']['fragments']} fragments on "
                         f"{run.cell.chips} chips")
    store = CSRStore.from_parts(ds["n"], ds["indptr"], ds["indices"])
    run.say(f"graph: {store.n_vertices} vertices, {store.n_edges} arcs")
    mesh = Mesh(np.array(jax.devices()[:run.cell.chips]), ("data",))
    return flexbuild(store, cfg["bricks"], mesh=mesh).engine("grape")


def rotation(engine, job: dict, source: int) -> dict:
    from repro.engines.grape import algorithms

    calls = {"bfs": lambda: algorithms.bfs(engine, source),
             "pagerank": lambda: algorithms.pagerank(
                 engine, job["damping"], max_steps=job["pagerank_steps"],
                 tol=0.0),
             "wcc": lambda: algorithms.wcc(engine)}
    out = {}
    for name in ALGOS:
        with TraceAnnotation(f"bench.{name}"):
            out[name] = np.asarray(calls[name]())
    return out


def setup(run) -> None:
    t = time.perf_counter()
    engine = build_engine(run)
    run.say(f"set-up: store, deployment and fragments "
            f"{time.perf_counter() - t!r} s")
    t = time.perf_counter()
    rotation(engine, run.cell.mix, run.dataset["source"])
    run.say(f"set-up: first rotation, compile or cache load included, "
            f"{time.perf_counter() - t!r} s; device bytes in use "
            f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in jax.devices()[:run.cell.chips]]}")
    run.program["engine"] = engine


def window(run) -> None:
    engine, job = run.program["engine"], run.cell.mix
    answers = []
    t_open = time.perf_counter()
    deadline = t_open + run.seconds
    while True:
        answers.append(rotation(engine, job, run.dataset["source"]))
        now = time.perf_counter()
        if now >= deadline:
            break
    run.window_s = now - t_open
    run.attempted = len(ALGOS) * len(answers)
    run.failed = sum(bool(np.isnan(a["pagerank"]).any()) for a in answers)
    run.extra.update(answers=answers, window_runs=run.attempted)
    run.say(f"window: {len(answers)} rotations in {run.window_s!r} s")
