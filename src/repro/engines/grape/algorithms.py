"""Built-in analytics library (paper's application layer ⑤) over
Pregel / PIE / FLASH. Each algorithm has a pure-numpy oracle in the tests.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engines.grape.engine import GrapeEngine
from repro.engines.grape.flash import FlashContext
from repro.engines.grape.pie import PIEProgram, run_pie
from repro.engines.grape.pregel import VertexProgram, run_pregel


def _pad_state(arr, n: int, fill) -> jnp.ndarray:
    """A warm-start vector comes trimmed to the store's vertex range; pad
    it back out to the engine's fragment width (``fill``: scalar, or
    ``"iota"`` for identity labels) so padding rows start from the same
    values a cold init would give them."""
    arr = jnp.asarray(arr, jnp.float32)
    if arr.shape[0] >= n:
        return arr[:n]
    if fill == "iota":
        tail = jnp.arange(arr.shape[0], n, dtype=jnp.float32)
    else:
        tail = jnp.full((n - arr.shape[0],), fill, jnp.float32)
    return jnp.concatenate([arr, tail])


# ----------------------------------------------------------------- PageRank
def pagerank(engine: GrapeEngine, damping: float = 0.85,
             max_steps: int = 50, tol: float = 1e-6,
             warm_start=None) -> jnp.ndarray:
    """``warm_start`` (a previous snapshot's rank vector) restarts the
    contraction from that solution instead of uniform: it converges to the
    same fixpoint TOLERANCE as a cold start — results agree with cold
    start to within ``tol/(1-damping)`` in L1, not bit-exactly (the
    documented incremental contract, DESIGN.md §15)."""
    n = engine.frags.n_vertices

    prog = VertexProgram(
        init=lambda n_: {"rank": jnp.full((n_,), 1.0 / n_, jnp.float32)},
        send=lambda st, deg: st["rank"] / jnp.maximum(deg, 1.0),
        update=lambda st, msgs, step: {
            "rank": (1.0 - damping) / n + damping * msgs},
        combiner="sum",
        residual_key="rank",
        tol=tol,
    )
    init_state = None
    if warm_start is not None:
        init_state = {"rank": _pad_state(warm_start, n, 0.0)}
    return run_pregel(engine, prog, max_steps,
                      cache_key=("pagerank", damping, max_steps, tol),
                      init_state=init_state)["rank"]


# ---------------------------------------------------------------------- BFS
def bfs(engine: GrapeEngine, source: int, max_steps: int = 64,
        warm_start=None) -> jnp.ndarray:
    """``warm_start`` (a previous snapshot's depth vector for the SAME
    source) is a valid upper bound on an append-only graph, so monotone
    min-propagation from it reaches the unique fixpoint BIT-EXACTLY
    (DESIGN.md §15)."""
    n = engine.frags.n_vertices
    inf = jnp.float32(jnp.inf)

    def init(n_):
        d = jnp.full((n_,), inf, jnp.float32)
        return {"depth": d.at[source].set(0.0)}

    prog = VertexProgram(
        init=init,
        send=lambda st, deg: st["depth"] + 1.0,
        update=lambda st, msgs, step: {
            "depth": jnp.minimum(st["depth"], msgs)},
        combiner="min",
        residual_key="depth",
        tol=0.0,
    )
    init_state = None
    if warm_start is not None:
        d = _pad_state(warm_start, n, jnp.inf).at[source].set(0.0)
        init_state = {"depth": d}
    return run_pregel(engine, prog, max_steps,
                      cache_key=("bfs", source, max_steps),
                      init_state=init_state)["depth"]


# --------------------------------------------------------------------- SSSP
def sssp(engine: GrapeEngine, source: int, max_steps: int = 128,
         warm_start=None) -> jnp.ndarray:
    """``warm_start`` (a previous snapshot's distance vector for the SAME
    source): on an append-only graph (edges added, existing weights
    immutable) old distances upper-bound new ones and every relaxation
    candidate is the same left-associated path sum, so the min-plus
    fixpoint is reached bit-exactly (DESIGN.md §15)."""
    inf = jnp.float32(jnp.inf)

    def init(n_):
        d = jnp.full((n_,), inf, jnp.float32)
        return {"dist": d.at[source].set(0.0)}

    prog = VertexProgram(
        init=init,
        send=lambda st, deg: st["dist"],          # + w applied by engine
        update=lambda st, msgs, step: {
            "dist": jnp.minimum(st["dist"], msgs)},
        combiner="min",
        use_weights=True,
        residual_key="dist",
        tol=0.0,
    )
    init_state = None
    if warm_start is not None:
        n = engine.frags.n_vertices
        d = _pad_state(warm_start, n, jnp.inf).at[source].set(0.0)
        init_state = {"dist": d}
    return run_pregel(engine, prog, max_steps,
                      cache_key=("sssp", source, max_steps),
                      init_state=init_state)["dist"]


# ---------------------------------------------------------------------- WCC
def wcc(engine: GrapeEngine, max_steps: int = 64,
        warm_start=None) -> jnp.ndarray:
    """Weakly-connected components by min-label propagation (assumes the
    graph was symmetrized by the caller for true WCC). ``warm_start`` (a
    previous snapshot's labels) upper-bounds the new labels on an
    append-only graph — components only merge — so the min-label fixpoint
    is reached bit-exactly (DESIGN.md §15)."""
    prog = VertexProgram(
        init=lambda n_: {"lab": jnp.arange(n_, dtype=jnp.float32)},
        send=lambda st, deg: st["lab"],
        update=lambda st, msgs, step: {"lab": jnp.minimum(st["lab"], msgs)},
        combiner="min",
        residual_key="lab",
        tol=0.0,
    )
    init_state = None
    if warm_start is not None:
        init_state = {"lab": _pad_state(warm_start,
                                        engine.frags.n_vertices, "iota")}
    return run_pregel(engine, prog, max_steps, cache_key=("wcc", max_steps),
                      init_state=init_state)["lab"].astype(jnp.int32)


# ----------------------------------------------------- equity shares (§8)
def equity_shares(engine: GrapeEngine, holder_mask: np.ndarray,
                  max_steps: int = 30, tol: float = 1e-7) -> jnp.ndarray:
    """The paper's Equity Analysis: propagate ownership shares along weighted
    invest edges until fixpoint; returns effective share of each *holder*
    vertex in every company it (transitively) owns, aggregated per vertex.

    state: for each vertex, total share attributable to ultimate holders is
    obtained by propagating holder-rooted mass along edge weights."""
    n = engine.frags.n_vertices
    hm = jnp.asarray(holder_mask, jnp.float32)

    prog = VertexProgram(
        init=lambda n_: {"share": hm},
        send=lambda st, deg: st["share"],
        update=lambda st, msgs, step: {"share": hm + msgs},
        combiner="sum",
        use_weights=True,
        residual_key="share",
        tol=tol,
    )
    # no cache_key: the program closes over holder_mask, which may differ
    # between calls (a cached closure would silently reuse the old mask)
    return run_pregel(engine, prog, max_steps)["share"]


# ------------------------------------------------------------- PIE PageRank
def pagerank_pie(engine: GrapeEngine, damping: float = 0.85,
                 rounds: int = 30) -> jnp.ndarray:
    """PageRank in the PIE model: PEval runs local iterations on the
    fragment-internal edges, IncEval folds in cross-fragment mass."""
    n = engine.frags.n_vertices

    def peval(eng):
        rank = jnp.full((n,), 1.0 / n, jnp.float32)
        emitted = rank / jnp.maximum(eng.out_degree.astype(jnp.float32), 1.0)
        return {"rank": rank}, emitted

    def inc(state, msgs, r):
        rank = (1.0 - damping) / n + damping * msgs
        emitted = rank / jnp.maximum(engine.out_degree.astype(jnp.float32), 1.0)
        return {"rank": rank}, emitted

    prog = PIEProgram(peval=peval, inc=inc,
                      assemble=lambda st: st,
                      combiner="sum", residual_key="rank", tol=1e-6)
    return run_pie(engine, prog, rounds)["rank"]


# ------------------------------------------------------------- FLASH: k-core
def kcore(engine: GrapeEngine, k: int, max_rounds: int = 64) -> jnp.ndarray:
    """FLASH-style k-core: iteratively peel vertices with degree < k.
    Returns a boolean mask of the k-core."""
    ctx = FlashContext(engine)
    alive = ctx.all_vertices()
    deg = ctx.deg
    for _ in range(max_rounds):
        # degree counting restricted to alive endpoints: push 1 from alive
        # vertices, mask at receivers
        inbox = ctx.push(alive, jnp.ones_like(deg))
        cur_deg = jnp.where(alive, inbox, 0.0)
        new_alive = alive & (cur_deg >= k)
        if bool(jnp.all(new_alive == alive)):
            break
        alive = new_alive
    return alive


# ------------------------------------- FLASH: CC with pointer jumping
def cc_pointer_jumping(engine: GrapeEngine, max_rounds: int = 32) -> jnp.ndarray:
    """Connected components via label propagation + pointer jumping — the
    FLASH-only pattern (pointer jumping reads labels at *non-neighbor*
    vertices)."""
    ctx = FlashContext(engine)
    n = ctx.n
    lab = jnp.arange(n, dtype=jnp.float32)
    alive = ctx.all_vertices()
    for _ in range(max_rounds):
        inbox = ctx.push(alive, lab, combiner="min")
        new_lab = jnp.minimum(lab, inbox)
        # pointer jumping: lab[v] = lab[lab[v]] (non-neighbor gather)
        jumped = ctx.pull_at(new_lab, new_lab.astype(jnp.int32))
        new_lab = jnp.minimum(new_lab, jumped)
        if bool(jnp.all(new_lab == lab)):
            break
        lab = new_lab
    return lab.astype(jnp.int32)


# ------------------------------------------------ FLASH: triangle counting
def triangle_count(engine: GrapeEngine) -> int:
    """Per-edge common-neighbor intersection via N-bit membership blocks —
    the FLASH non-neighbor pattern (each edge probes arbitrary vertex rows).

    Counts directed triangles u→v→w→…: Σ_(u,v)∈E |N(u) ∩ N(v)| over the
    out-adjacency. Dense bitset rows keep it vectorized (N ≤ ~16k)."""
    fa = engine.frags
    n = fa.n_vertices
    # dense boolean adjacency per fragment row block (vectorized probe)
    import numpy as np

    indices = np.asarray(fa.indices)
    e_src = np.asarray(fa.e_src)
    mask = np.asarray(fa.e_mask)
    adj = np.zeros((n, n), bool)
    for f in range(fa.indices.shape[0]):
        src_global = e_src[f] + f * fa.v_per_frag
        valid = mask[f]
        adj[src_global[valid], indices[f][valid]] = True
    # per-edge intersection: Σ_e |N(u)∩N(v)|
    total = 0
    for f in range(fa.indices.shape[0]):
        valid = mask[f]
        u = (e_src[f] + f * fa.v_per_frag)[valid]
        v = indices[f][valid]
        total += int(np.sum(adj[u] & adj[v]))
    return total


# ------------------------------------------------- LPA (community, mode)
def lpa_communities(engine: GrapeEngine, max_rounds: int = 20,
                    n_buckets: int = 64, seed: int = 0) -> jnp.ndarray:
    """Label propagation with mode aggregation, approximated by hashed
    one-hot bucket voting (dense [N, B] message matrix — the compact-buffer
    exchange carries B floats per vertex)."""
    ctx = FlashContext(engine)
    n = ctx.n
    import numpy as np
    rng = np.random.default_rng(seed)
    bucket_of = jnp.asarray(rng.integers(0, n_buckets, n))
    lab = jnp.arange(n, dtype=jnp.int32)
    for _ in range(max_rounds):
        votes, mins = [], []
        for b in range(n_buckets):
            in_bucket = bucket_of[lab] == b
            votes.append(ctx.push(ctx.all_vertices(),
                                  in_bucket.astype(jnp.float32)))
            mins.append(ctx.push(ctx.all_vertices(),
                                 jnp.where(in_bucket,
                                           lab.astype(jnp.float32), jnp.inf),
                                 combiner="min"))
        votes = jnp.stack(votes, axis=1)                     # [N, B]
        mins = jnp.stack(mins, axis=1)                       # [N, B]
        best_bucket = jnp.argmax(votes, axis=1)
        cand = jnp.take_along_axis(mins, best_bucket[:, None], axis=1)[:, 0]
        has_in = jnp.sum(votes, axis=1) > 0
        new_lab = jnp.where(has_in & jnp.isfinite(cand),
                            cand.astype(jnp.int32), lab)
        if bool(jnp.all(new_lab == lab)):
            break
        lab = new_lab
    return lab


# ---------------------------------------------------------- degree metrics
def degree_centrality(engine: GrapeEngine) -> jnp.ndarray:
    """In-degree centrality via one compact-buffer superstep."""
    ctx = FlashContext(engine)
    inbox = ctx.push(ctx.all_vertices(),
                     jnp.ones((ctx.n,), jnp.float32))
    return inbox / jnp.maximum(ctx.n - 1, 1)


# ----------------------------------------------------- numpy oracles (tests)
def triangle_count_numpy(indptr, indices):
    import numpy as np
    n = len(indptr) - 1
    adj = np.zeros((n, n), bool)
    src = np.repeat(np.arange(n), np.diff(indptr))
    adj[src, indices] = True
    return int(sum(np.sum(adj[u] & adj[v]) for u, v in zip(src, indices)))


def pagerank_numpy(indptr, indices, damping=0.85, iters=50):
    n = len(indptr) - 1
    deg = np.maximum(np.diff(indptr), 1)
    src = np.repeat(np.arange(n), np.diff(indptr))
    rank = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.zeros(n)
        np.add.at(contrib, indices, rank[src] / deg[src])
        new = (1 - damping) / n + damping * contrib
        if np.abs(new - rank).sum() < 1e-6:
            rank = new
            break
        rank = new
    return rank


def bfs_numpy(indptr, indices, source):
    n = len(indptr) - 1
    depth = np.full(n, np.inf)
    depth[source] = 0
    frontier = [source]
    d = 0
    while frontier:
        nxt = []
        for u in frontier:
            for w in indices[indptr[u]:indptr[u + 1]]:
                if depth[w] == np.inf:
                    depth[w] = d + 1
                    nxt.append(int(w))
        frontier = nxt
        d += 1
    return depth


def sssp_numpy(indptr, indices, weights, source):
    n = len(indptr) - 1
    dist = np.full(n, np.inf)
    dist[source] = 0.0
    for _ in range(n):
        changed = False
        src = np.repeat(np.arange(n), np.diff(indptr))
        cand = dist[src] + weights
        best = np.full(n, np.inf)
        np.minimum.at(best, indices, cand)
        new = np.minimum(dist, best)
        if np.allclose(new, dist, equal_nan=True):
            break
        dist = new
        changed = True
        if not changed:
            break
    return dist
