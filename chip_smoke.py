"""Bring-up run of the served graph stack on one accelerator chip.

Drives the main path once through the entry points a user calls —
``flexbuild`` → ``FlexSession`` → the ``serve_async()`` front door →
route → engine → device program → host finish — on an LDBC-SNB-SF1-sized
graph (875,000 vertices, 16.5M edges), and checks every answer against
an independent reference over the same snapshot.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four chips: sharded GRAPE only

Without a TPU the full size is refused. A CPU rehearsal names a smaller
size, runs every phase, and still ends ``"ok": false``:

    JAX_PLATFORMS=cpu python chip_smoke.py --persons 2000
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chip_smoke.py --chips 4 --scale 12

The last line of standard output is one JSON object,
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
Earlier lines give each phase's wall seconds (set-up, compilation
included) and the device's peak bytes in use: bring-up diagnostics, not
speed metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

# The served deployment: snb_store at the edge count of LDBC SNB SF1
# (about 17M) under this repository's Person/Item/Post schema, with the
# ogbn-products feature width and class count for the learning verb.
N_PERSONS = 500_000
FEAT_DIM = 100
N_CLASSES = 47
SEED = 0
GRAPH_BRICKS = ("gart", "cypher", "gremlin", "gaia", "hiactor", "grape",
                "pregel", "pie", "flash", "graphlearn", "sage")

# The sharded deployment: Graph500 RMAT at its edge factor.
RMAT_SCALE = 21
RMAT_EDGE_FACTOR = 16
N_CHIPS_SHARDED = 4

FUTURE_TIMEOUT_S = 900.0
PAGERANK_DAMPING = 0.85
# Relative L1 distance between the float32 device ranks and the float64
# pagerank_numpy ranks. Convergence contributes at most 2·tol/(1−d) ≈
# 1.3e-5 absolute; float32 scatter-add accumulation over a hub's in-edges
# dominates and grows with the hub's in-degree: on this graph family the
# CPU backend measured 5e-6 relative at 35k vertices and 2.6e-5 at 175k.
PAGERANK_REL_L1_TOL = 1e-3
SHARDED_PAGERANK_LINF_TOL = 1e-5

W_CREATE = ("MATCH (a:Person {id: $x}), (b:Person {id: $y}) "
            "CREATE (a)-[:KNOWS]->(b)")
W_SET = "MATCH (a:Person {id: $x}) SET a.credits = $c"
POINT = "MATCH (a:Person {id: $x}) RETURN a.credits AS c"
TWO_HOP_TOPK = ("MATCH (a:Person)-[:KNOWS]->(b:Person)-[:BUY]->(c:Item) "
                "WHERE a.credits < $t WITH c, COUNT(*) AS k "
                "RETURN c AS c, k AS k ORDER BY k DESC LIMIT 10")
KNOWS_1_2 = ("MATCH (a:Person)-[:KNOWS*1..2]->(b:Person) "
             "WHERE a.id >= $lo AND a.id < $hi AND b.credits < $t "
             "RETURN b AS b")
SHORTEST = ("MATCH p = shortestPath((a:Person)-[:KNOWS*1..4]->(b:Person)) "
            "WHERE a.id >= $lo AND a.id < $hi AND b.credits < $t "
            "RETURN b AS b, dist AS d")
PAGERANK_TOP = ("CALL algo.pagerank($d) YIELD v, rank "
                "RETURN v AS v, rank AS r ORDER BY r DESC LIMIT 10")
GNN_INFER = "CALL gnn.infer($m) YIELD v, score RETURN v AS v, score AS sc"


class SmokeFailure(Exception):
    """A served answer took the wrong route or disagreed with its
    reference."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"phase {name}: {time.perf_counter() - t0!r} s wall "
          f"(set-up, compilation included)", flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def bag_equal(ref, got) -> bool:
    """Same columns and the same multiset of rows, exactly."""
    if set(ref) != set(got):
        return False
    cols = sorted(ref)
    if not cols:
        return True
    a = np.stack([np.asarray(ref[c], np.float64).ravel() for c in cols], 1)
    b = np.stack([np.asarray(got[c], np.float64).ravel() for c in cols], 1)
    if a.shape != b.shape:
        return False
    a = a[np.lexsort(a.T[::-1])]
    b = b[np.lexsort(b.T[::-1])]
    return bool(np.array_equal(a, b))


def n_rows(result) -> int:
    return len(next(iter(result.values()))) if result else 0


def interpreter(session):
    """A fresh interpreter over the session's current snapshot. It shares
    only the procedure registry (so a CALL reads the same memoized
    fixpoint); plans, routes and device state are its own."""
    from repro.engines.gaia import GaiaEngine

    return GaiaEngine(session.snapshot_store, procedures=session.procedures)


# ---------------------------------------------------------------- one chip
def build_session(persons: int):
    from repro.core.flexbuild import flexbuild
    from repro.storage.gart import GARTStore
    from repro.storage.generators import snb_store

    with phase("build store"):
        cs = snb_store(n_persons=persons, n_items=persons // 2,
                       n_posts=persons // 4, seed=SEED)
        rng = np.random.default_rng(SEED)
        cs._vprops["feat"] = rng.standard_normal(
            (cs.n_vertices, FEAT_DIM), dtype=np.float32)
        cs._vprops["label"] = rng.integers(
            0, N_CLASSES, cs.n_vertices).astype(np.int32)
        store = GARTStore.from_csr(cs)
    print(f"graph: {store.n_vertices} vertices, {store.n_edges} edges, "
          f"feat width {FEAT_DIM}", flush=True)
    with phase("flexbuild"):
        session = flexbuild(store, GRAPH_BRICKS, feature_prop="feat",
                            label_prop="label", serve=True)
    return session


def read_requests(persons: int):
    """(name, template, params, expected route). Anchors start at the
    middle of the person ids: the zipf KNOWS hubs sit at the low ids, and
    walks through them would push float32 path counts toward the 2^24
    cliff and the interpreter's row tables past what a check can hold."""
    lo = persons // 2
    return [
        ("point", POINT, {"x": lo}, "hiactor"),
        ("point", POINT, {"x": lo + 7}, "hiactor"),
        ("two_hop_topk", TWO_HOP_TOPK, {"t": 10}, "fragment"),
        ("two_hop_topk", TWO_HOP_TOPK, {"t": 20}, "fragment"),
        ("knows_1_2", KNOWS_1_2, {"lo": lo, "hi": lo + 4, "t": 10},
         "fragment"),
        ("knows_1_2", KNOWS_1_2, {"lo": lo + 100, "hi": lo + 102, "t": 10},
         "fragment"),
        ("shortest", SHORTEST, {"lo": lo, "hi": lo + 2, "t": 10},
         "fragment"),
        ("pagerank", PAGERANK_TOP, {"d": PAGERANK_DAMPING}, "grape"),
    ]


def serve_reads(session, sched, persons: int):
    """Every read rides its route and equals the interpreter's bag."""
    reqs = read_requests(persons)
    futs = [sched.submit(tmpl, params) for _, tmpl, params, _ in reqs]
    oracle = interpreter(session)
    answers = []
    for (name, tmpl, params, route), fut in zip(reqs, futs):
        resp = fut.result(timeout=FUTURE_TIMEOUT_S)
        check(resp.engine == route,
              f"{name} {params}: rode {resp.engine!r}, expected {route!r}")
        ref = oracle.execute_plan(oracle.compile(tmpl), params=params)
        check(bag_equal(ref, resp.result),
              f"{name} {params}: answer differs from the interpreter")
        print(f"read {name} {params}: route {resp.engine}, "
              f"{n_rows(resp.result)} rows, equal to the interpreter",
              flush=True)
        answers.append(resp.result)
    return answers


def check_pagerank(session, served) -> None:
    """The whole rank vector and the served top-10 against the plain
    numpy power iteration on the same snapshot's adjacency."""
    from repro.engines.grape.algorithms import pagerank_numpy

    indptr, indices = session.snapshot_store.adjacency()
    ref = pagerank_numpy(indptr, indices, damping=PAGERANK_DAMPING)
    got = np.asarray(session.analytical().run("pagerank", PAGERANK_DAMPING),
                     np.float64)
    rel_l1 = float(np.abs(got - ref).sum() / np.abs(ref).sum())
    rel_max = float(np.max(np.abs(got - ref) / ref))
    print(f"pagerank: relative L1 distance to pagerank_numpy {rel_l1!r} "
          f"(bound {PAGERANK_REL_L1_TOL!r}), largest elementwise relative "
          f"error {rel_max!r}", flush=True)
    check(rel_l1 <= PAGERANK_REL_L1_TOL,
          f"pagerank relative L1 {rel_l1} > {PAGERANK_REL_L1_TOL}")
    v = np.asarray(served["v"], np.int64)
    r = np.asarray(served["r"], np.float64)
    check(np.array_equal(r, got[v]),
          "served pagerank rows differ from the memoized rank vector")
    # a valid top-k of the reference up to the tolerance: ties at the
    # cut may fall either way, nothing below it may enter
    kth = np.sort(ref)[-len(v)]
    check(bool(np.all(ref[v] >= kth * (1 - PAGERANK_REL_L1_TOL))),
          "served pagerank top rows are not the reference's top rows")


def pick_new_neighbor(session, x: int, persons: int, t: int) -> int:
    """A person past the anchors whose credits pass KNOWS_1_2's endpoint
    filter, so a new x→y edge must add a row to that read."""
    credits = np.asarray(session.snapshot_store.vertex_prop("credits"))
    cand = np.nonzero(credits[:persons] < t)[0]
    cand = cand[cand >= x + 4]
    check(len(cand) > 0, "no write target passes the endpoint filter")
    return int(cand[0])


def serve_writes(session, sched, persons: int, pre_write) -> None:
    """CREATE and SET through the front door, then a fragment read that
    must equal a fresh interpreter AND differ from the pre-write answer
    (stale device slabs would serve the old bag)."""
    lo = persons // 2
    params = {"lo": lo, "hi": lo + 4, "t": 10}
    x = lo
    y = pick_new_neighbor(session, x, persons, params["t"])
    v0 = session.version
    writes = [sched.submit(W_CREATE, {"x": x, "y": y}),
              sched.submit(W_SET, {"x": x, "c": 123})]
    for f in writes:
        resp = f.result(timeout=FUTURE_TIMEOUT_S)
        check(resp.engine == "write", f"write rode {resp.engine!r}")
    check(session.version is not None and session.version > v0,
          f"session version {session.version} did not advance past {v0}")
    post = sched.submit(KNOWS_1_2, params).result(timeout=FUTURE_TIMEOUT_S)
    check(post.engine == "fragment",
          f"post-write read rode {post.engine!r}, expected 'fragment'")
    oracle = interpreter(session)
    ref = oracle.execute_plan(oracle.compile(KNOWS_1_2), params=params)
    check(bag_equal(ref, post.result),
          "post-write read differs from a fresh interpreter")
    check(not bag_equal(pre_write, post.result),
          "post-write read equals the pre-write answer: stale slabs")
    credit = sched.submit(POINT, {"x": x}).result(timeout=FUTURE_TIMEOUT_S)
    check(credit.engine == "hiactor"
          and np.asarray(credit.result["c"]).tolist() == [123],
          f"SET not read back: {credit.engine} {credit.result}")
    print(f"writes: version {v0} -> {session.version}; post-write read "
          f"{n_rows(post.result)} rows (was {n_rows(pre_write)}), equal to "
          f"a fresh interpreter", flush=True)


def learn_and_infer(session, sched) -> None:
    """The fused sample→gather→SGD step for a few steps, then the trained
    model served through CALL gnn.infer, bit-equal to infer_scores."""
    lrn = session.learning()
    with phase("train"):
        trainer = lrn.trainer(hidden=256, n_classes=N_CLASSES,
                              fanouts=(15, 10),
                              sampler=lrn.sampler(backend="device"),
                              backend="device", batch_size=1024)
        _, losses = trainer.train(steps=5)
    print(f"train: losses {losses!r}", flush=True)
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    lrn.register_inference(trainer, name="sage")
    with phase("gnn.infer"):
        resp = sched.submit(GNN_INFER, {"m": "sage"}).result(
            timeout=FUTURE_TIMEOUT_S)
    check(resp.engine == "grape", f"gnn.infer rode {resp.engine!r}")
    want = np.asarray(trainer.infer_scores(), np.float32)
    v = np.asarray(resp.result["v"], np.int64)
    check(np.array_equal(np.sort(v), np.arange(len(want))),
          "gnn.infer did not answer every vertex once")
    got = np.empty_like(want)
    got[v] = np.asarray(resp.result["sc"], np.float32)
    check(np.array_equal(got.view(np.uint32), want.view(np.uint32)),
          "gnn.infer scores are not bit-equal to infer_scores()")
    print(f"gnn.infer: {len(v)} scores bit-equal to infer_scores()",
          flush=True)


def run_served(persons: int) -> None:
    session = build_session(persons)
    sched = session.serve_async()
    try:
        with phase("reads"):
            answers = serve_reads(session, sched, persons)
        with phase("pagerank reference"):
            check_pagerank(session, answers[-1])
        with phase("writes"):
            serve_writes(session, sched, persons, answers[4])
        learn_and_infer(session, sched)
    finally:
        session.close()
    check(sched.internal_error is None,
          f"scheduler latched {sched.internal_error!r}")
    check(session.last_publish_error is None,
          f"version bus raised {session.last_publish_error!r}")


# ------------------------------------------------------------- four chips
def run_sharded(scale: int) -> None:
    """pagerank, wcc and sssp on a 4-chip mesh against the same engine
    unsharded on device 0 of this process."""
    from repro.core.flexbuild import flexbuild
    from repro.engines.grape import GrapeEngine, algorithms as alg
    from repro.storage.generators import rmat_store

    check(len(jax.devices()) >= N_CHIPS_SHARDED,
          f"--chips {N_CHIPS_SHARDED} needs {N_CHIPS_SHARDED} devices, "
          f"found {len(jax.devices())}")
    print(f"rmat scale {scale}, edge factor {RMAT_EDGE_FACTOR}", flush=True)
    with phase("rmat graph"):
        g = rmat_store(scale=scale, edge_factor=RMAT_EDGE_FACTOR, seed=SEED)
    print(f"graph: {g.n_vertices} vertices, {g.n_edges} edges", flush=True)
    with phase("flexbuild sharded"):
        mesh = jax.make_mesh((N_CHIPS_SHARDED,), ("data",))
        sharded = flexbuild(g, ["grape", "pregel"],
                            mesh=mesh).engine("grape")
    with phase("single-device engine"):
        local = GrapeEngine(g, n_frags=N_CHIPS_SHARDED)
    # vertex 0 owns RMAT's heaviest quadrant: the widest sssp source
    algos = (("pagerank", alg.pagerank), ("wcc", alg.wcc),
             ("sssp", lambda e: alg.sssp(e, source=0)))
    for name, fn in algos:
        with phase(f"{name} sharded"):
            got = np.asarray(fn(sharded))
        with phase(f"{name} single device"):
            want = np.asarray(fn(local))
        if name == "pagerank":
            linf = float(np.abs(got - want).max())
            print(f"pagerank: L-inf sharded vs single device {linf!r}",
                  flush=True)
            check(linf <= SHARDED_PAGERANK_LINF_TOL,
                  f"sharded pagerank L-inf {linf}")
        else:
            check(np.array_equal(got, want),
                  f"sharded {name} differs from single device")
            print(f"{name}: sharded equals single device exactly",
                  flush=True)


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, N_CHIPS_SHARDED),
                    default=1,
                    help="1: the served path; 4: sharded GRAPE only")
    ap.add_argument("--persons", type=int, default=None,
                    help="CPU rehearsal only: shrink the served graph")
    ap.add_argument("--scale", type=int, default=None,
                    help="CPU rehearsal only: shrink the RMAT graph")
    args = ap.parse_args(argv)

    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"devices: {devices}", flush=True)
    print(f"device: platform {device['platform']}, kind {device['kind']}, "
          f"count {device['count']}; compile cache {cache_dir}", flush=True)

    on_tpu = dev.platform == "tpu"
    rehearsal = (args.persons if args.chips == 1 else args.scale) is not None
    if not on_tpu and not rehearsal:
        print("no TPU: the full size runs on the chip only; pass "
              "--persons (or --scale with --chips 4) to rehearse",
              file=sys.stderr)
        print(json.dumps({"ok": False, "device": device}))
        return 1

    ok = True
    try:
        if args.chips == 1:
            run_served(args.persons or N_PERSONS)
        else:
            run_sharded(args.scale or RMAT_SCALE)
    except Exception:            # noqa: BLE001 — any failed phase fails
        traceback.print_exc()
        ok = False
    print(f"device peak bytes in use: {peak_bytes()}", flush=True)
    print(f"compile cache: {cache_events['hits']} hits, "
          f"{cache_events['misses']} misses", flush=True)
    ok = ok and on_tpu
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
