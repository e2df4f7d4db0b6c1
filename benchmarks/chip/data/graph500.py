"""A Graph500 Kronecker graph, drawn on the cell's chips from a seed, in
the CSR form of an undirected graph with one balanced edge-cut fragment
per chip.

The draw is the Graph500 reference generator's: each edge picks one
quadrant of the initiator ``[[A, B], [C, D]]`` per level for ``scale``
levels, ``2 ** scale`` vertex ids. Self-loops and repeated edges are
dropped, as Graphalytics' datasets drop them. The generator draws a few
per cent more edges than ``edge_factor · 2 ** scale`` and keeps the
first ``n_edges`` distinct ones in the order drawn, so that every seed
gives a graph of the same size and every program the same shapes.

The draw comes from a fixed key, the configuration's ``structure_seed``;
the run's seed scrambles the vertex ids by a random permutation, as the
Graph500 generator scrambles them, balanced so that each fragment of
``2 ** scale / fragments`` contiguous ids holds exactly
``2 · n_edges / fragments`` arcs (``balanced_perm``), as an edge-cut
partitioner balances its fragments, and with the hub named 0
(``hub_first``). So every seed gives one graph under another naming and
another edge cut, and a kernel whose supersteps follow the graph's shape
(BFS from the hub, WCC's least-id labels) takes as many on every seed:
the seed does not change a run's work.

Each undirected edge is stored as two arcs, sorted by (source, target).
Dedup and the arcs' sort run per chip: edges go to the chip their hash
names, arcs to the chip whose fragment holds their source, each by one
``all_to_all``; the host receives the CSR arrays that the program's
immutable store adopts as they are.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks.chip.harness import seed_key

SENT = np.iinfo(np.int32).max
AXIS = "data"


def _slack(count: int) -> int:
    """Room above the mean in one (chip, bucket) slot of an exchange: a
    hashed bucket's count spreads by about the square root of its mean."""
    return 8 * math.isqrt(count) + 1024


def _bucket(h, n: int):
    """Top bits of a 32-bit hash → ``0 .. n-1``."""
    return ((h >> 16) * jnp.uint32(n)) >> 16


def _hash(a, b):
    h = (a.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) ^ (
        b.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    return h ^ (h >> 12)


def _exchange(dest, payload, n_dest: int, cap: int):
    """Send each row to chip ``dest`` (``n_dest`` or more: dropped) in
    ``cap`` slots per chip pair; returns what this chip received, its
    rows padded with ``SENT``, and how far the fullest slot overflowed."""
    slot = jnp.full(dest.shape, n_dest * cap, jnp.int32)
    over = jnp.int32(-cap)
    for d in range(n_dest):
        here = dest == d
        rank = jnp.cumsum(here, dtype=jnp.int32) - 1
        slot = jnp.where(here & (rank < cap), d * cap + rank, slot)
        over = jnp.maximum(over, rank[-1] + 1 - cap)
    sent = []
    for p in payload:
        rows = jnp.full((n_dest * cap,), SENT, p.dtype).at[slot].set(
            p, mode="drop")
        sent.append(jax.lax.all_to_all(rows.reshape(n_dest, cap), AXIS, 0,
                                       0).reshape(-1))
    return sent, over


@functools.lru_cache(maxsize=None)
def _dedupe_fn(mesh, scale, per_chip, cap, n_edges, thresholds):
    """The Kronecker draw, its dedup and its first ``n_edges`` distinct
    edges: one program over the mesh."""
    n_chips = mesh.shape[AXIS]
    n = 2 ** scale
    t_ab, t_c, t_a = (jnp.uint32(t) for t in thresholds)

    def body(key):
        me = jax.lax.axis_index(AXIS)
        key = jax.random.fold_in(key, me)

        def level(lv, ij):
            i, j = ij
            bits = jax.random.bits(jax.random.fold_in(key, lv),
                                   (2, per_chip), jnp.uint32)
            ib = bits[0] > t_ab
            jb = bits[1] > jnp.where(ib, t_c, t_a)
            return (i | (ib.astype(jnp.int32) << lv),
                    j | (jb.astype(jnp.int32) << lv))

        zero = jnp.zeros((per_chip,), jnp.int32)
        i, j = jax.lax.fori_loop(0, scale, level, (zero, zero))
        a, b = jnp.minimum(i, j), jnp.maximum(i, j)
        order = me * per_chip + jnp.arange(per_chip, dtype=jnp.int32)
        dest = jnp.where(a == b, n_chips, _bucket(_hash(a, b), n_chips))
        (a, b, order), over = _exchange(dest.astype(jnp.int32),
                                        (a, b, order), n_chips, cap)
        a, b, order = jax.lax.sort((a, b, order), num_keys=2)
        first = (a != jnp.roll(a, 1)) | (b != jnp.roll(b, 1))
        first = first.at[0].set(True)
        group = jnp.cumsum(first, dtype=jnp.int32) - 1
        drawn_first = jnp.full(order.shape, SENT).at[group].min(order)
        # each distinct edge once, with the order it was first drawn in
        order = jnp.where(first & (a != SENT), drawn_first[group], SENT)

        def count(t):
            return jax.lax.psum(jnp.sum(order < t), AXIS)

        # the least t at which the edges first drawn before t are n_edges
        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = lo + (hi - lo) // 2
            enough = count(mid) >= n_edges
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

        t, _ = jax.lax.fori_loop(
            0, 32, halve, (jnp.int32(0), jnp.int32(n_chips * per_chip)))
        keep = order < t
        one = keep.astype(jnp.int32)
        deg = jnp.zeros((n,), jnp.int32).at[jnp.where(keep, a, 0)].add(
            one).at[jnp.where(keep, b, 0)].add(one)
        return (a, b, keep, jax.lax.psum(deg, AXIS), count(t),
                jax.lax.pmax(over, AXIS))

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(), P()),
        check_vma=False))


@functools.lru_cache(maxsize=None)
def _arcs_fn(mesh, n, arcs_per_frag, cap):
    """Both arcs of each kept edge, renamed by ``perm`` and sent to the
    chip whose fragment holds their source, sorted there into CSR rows."""
    n_chips = mesh.shape[AXIS]
    v_per = n // n_chips

    def body(a, b, keep, perm):
        me = jax.lax.axis_index(AXIS)
        src = jnp.concatenate([perm[a], perm[b]])
        dst = jnp.concatenate([perm[b], perm[a]])
        ok = jnp.concatenate([keep, keep])
        dest = jnp.where(ok, src // v_per, n_chips)
        (src, dst), over = _exchange(dest, (src, dst), n_chips, cap)
        src, dst = jax.lax.sort((src, dst), num_keys=2)
        got = jnp.sum(src != SENT)
        rows = me * v_per + jnp.arange(v_per + 1, dtype=jnp.int32)
        indptr = jnp.searchsorted(src, rows, side="left").astype(jnp.int32)
        return (dst[None, :arcs_per_frag], indptr[None],
                jax.lax.pmax(jnp.maximum(over, jnp.abs(got - arcs_per_frag)),
                             AXIS))

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=(P(AXIS), P(AXIS), P()), check_vma=False))


def balanced_perm(deg: np.ndarray, n_frags: int, rng) -> np.ndarray:
    """A random permutation of vertex ids (old → new) under which each
    range of ``len(deg) / n_frags`` new ids holds the same sum of
    degrees. Vertices are dealt to fragments in order of degree, ties in
    random order, back and forth (``0 1 2 3 3 2 1 0 ...``), so that each
    fragment holds its share of every degree; then a vertex of degree
    ``k`` in a fragment above the mean trades places with one of degree
    ``k - 1`` in a fragment below it, one arc a trade, until all are
    even. Within a fragment the ids are in random order."""
    n = len(deg)
    v_per = n // n_frags
    ties = rng.permutation(n)
    dealt = ties[np.argsort(-deg[ties], kind="stable")]
    col = np.arange(n) % n_frags
    frag = np.empty(n, np.int64)
    frag[dealt] = np.where((np.arange(n) // n_frags) % 2, n_frags - 1 - col,
                           col)
    target = int(deg.sum()) // n_frags
    while True:
        load = np.bincount(frag, weights=deg, minlength=n_frags).astype(
            np.int64)
        hi, lo = int(np.argmax(load)), int(np.argmin(load))
        need = min(load[hi] - target, target - load[lo])
        if need <= 0:
            break
        for k in range(1, int(deg.max()) + 1):
            xs = np.flatnonzero((frag == hi) & (deg == k))[:need]
            ys = np.flatnonzero((frag == lo) & (deg == k - 1))[:len(xs)]
            xs = xs[:len(ys)]
            frag[xs], frag[ys] = lo, hi
            need -= len(xs)
            if need == 0:
                break
        else:
            raise ValueError(f"cannot balance {n_frags} fragments of "
                             f"{target} arcs")
    perm = np.empty(n, np.int64)
    for f in range(n_frags):
        members = np.flatnonzero(frag == f)
        perm[members] = f * v_per + rng.permutation(v_per)
    return perm


def hub_first(perm: np.ndarray, deg: np.ndarray, v_per: int) -> np.ndarray:
    """``perm`` (old → new ids) with the vertex of highest degree, the
    least old id on ties, renamed 0: the block of ``v_per`` ids that holds
    it trades places with the first block, then it trades ids with the
    vertex named 0. Every fragment keeps its arcs."""
    hub = int(np.argmax(deg))
    block = perm // v_per
    f = block[hub]
    perm = np.where(block == f, perm - f * v_per,
                    np.where(block == 0, perm + f * v_per, perm))
    first = int(np.flatnonzero(perm == 0)[0])
    perm[first], perm[hub] = perm[hub], 0
    return perm


def generate(graph: dict, seed: int) -> dict:
    """CSR arrays of one graph: ``n``, ``indptr`` (int64), ``indices``
    (int32, targets sorted within each row), ``n_edges`` (undirected)
    and ``source``, the vertex of highest degree: 0 (``hub_first``)."""
    scale, frags = int(graph["scale"]), int(graph["fragments"])
    n, n_edges = 2 ** scale, int(graph["n_edges"])
    if n % frags or (2 * n_edges) % frags:
        raise ValueError(f"{n} vertices and {2 * n_edges} arcs do not "
                         f"split into {frags} equal fragments")
    devices = jax.devices()[:frags]
    if len(devices) < frags:
        raise ValueError(f"{frags} fragments need as many devices; JAX "
                         f"has {len(devices)}")
    mesh = Mesh(np.array(devices), (AXIS,))
    a, b, c = (float(graph[k]) for k in ("a", "b", "c"))
    thresholds = tuple(int(p * 2 ** 32) for p in (a + b, c / (1 - a - b),
                                                  a / (a + b)))
    drawn = int(graph["edge_factor"] * n * (1 + graph["draw_slack"]))
    per_chip = -(-drawn // frags)
    dedupe = _dedupe_fn(mesh, scale, per_chip,
                        per_chip // frags + _slack(per_chip // frags),
                        n_edges, thresholds)
    ea, eb, keep, deg, kept, over = dedupe(
        seed_key(int(graph["structure_seed"])))
    if int(over) > 0 or int(kept) != n_edges:
        raise ValueError(f"draw of {drawn} edges kept {int(kept)} of "
                         f"{n_edges} distinct (slot overflow {int(over)})")
    deg = np.asarray(deg).astype(np.int64)
    perm = hub_first(balanced_perm(deg, frags, np.random.default_rng(
        [seed & 0xFFFFFFFF, seed >> 32, 500])), deg, n // frags)
    per_frag = 2 * n_edges // frags
    arcs = _arcs_fn(mesh, n, per_frag, per_frag // frags
                    + _slack(per_frag // frags))
    indices, local_ptr, bad = arcs(ea, eb, keep,
                                   jnp.asarray(perm, jnp.int32))
    del ea, eb, keep
    if int(bad) > 0:
        raise ValueError(f"arcs do not fill their fragments ({int(bad)})")
    indices = np.asarray(indices).reshape(-1)
    local_ptr = np.asarray(local_ptr).astype(np.int64)
    indptr = np.empty(n + 1, np.int64)
    indptr[:-1] = (local_ptr[:, :-1] + (np.arange(frags) * per_frag)[:, None]
                   ).reshape(-1)
    indptr[-1] = 2 * n_edges
    new_deg = np.empty(n, np.int64)
    new_deg[perm] = deg
    return {"n": n, "indptr": indptr, "indices": indices,
            "n_edges": n_edges, "source": int(np.argmax(new_deg))}
