"""The plain references of the Graphalytics kernels, and the check that
decides ``correct``. It runs in numpy and SciPy on the host, on the
generated CSR arrays, and imports nothing of the program.

Every answer the window brought to the host is compared:

- BFS, by its certificate: the source's depth is 0 and every other
  vertex's depth is 1 + the least depth over its row, or inf where the
  row reaches nothing. With depths at least 0, the BFS distances are the
  one solution of these equations, so the check is exact.
  ``bfs_wrong`` counts the vertices that break them.
- PageRank, by its reference: the same iterations of GRAPE's update,
  ``(1 - d)/n + d · Σ_{u→v} rank(u)/max(deg(u), 1)``, in float64 from
  the uniform start, with no redistribution of dangling mass, as the
  program has none. ``pagerank_gap`` is the relative L1 gap.
- WCC, by its reference: components from SciPy (strong components of
  the stored arcs, which hold both directions of every edge), each
  labelled with its least id. ``wcc_wrong`` counts the vertices whose
  label differs.

Every row-wise pass splits the rows over threads, and the WCC reference
runs beside PageRank's: numpy's gathers and reductions and SciPy's
component search release the interpreter's lock.

Control (``run.control``): the references rounded through bfloat16 stand
in for the program's answers.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

# limits of the compared numbers, between the program's readings (lower)
# and the least of the bfloat16 control's (upper) on a 2x2 TPU v5e:
# PERF.md §4. BFS and WCC answers are exact.
LIMITS = {"bfs_wrong": 0, "pagerank_gap": 1e-4, "wcc_wrong": 0}

THREADS = min(16, os.cpu_count() or 1)


def row_reduce(op, values, indptr, indices, empty):
    """``op`` over ``values[indices]`` in each CSR row; ``empty`` for a
    row with no arcs."""
    n = len(indptr) - 1
    out = np.full(n, empty, values.dtype)
    even = np.linspace(0, indptr[-1], THREADS + 1)[1:-1]
    cuts = np.unique(np.concatenate(
        [[0], np.minimum(np.searchsorted(indptr, even), n), [n]]))

    def part(r0, r1):
        a0, a1 = indptr[r0], indptr[r1]
        full = np.diff(indptr[r0:r1 + 1]) > 0
        if a1 > a0:
            out[r0:r1][full] = op.reduceat(values[indices[a0:a1]],
                                           indptr[r0:r1][full] - a0)

    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(part, r0, r1)
                  for r0, r1 in zip(cuts[:-1], cuts[1:])]:
            f.result()
    return out


def bfs_wrong(depth, indptr, indices, source) -> int:
    d = np.asarray(depth, np.float64)
    want = row_reduce(np.minimum, d, indptr, indices, np.inf) + 1.0
    want[source] = 0.0
    return int(np.count_nonzero((d != want) | (d < 0)))


def bfs_reference(indptr, indices, source) -> np.ndarray:
    d = np.full(len(indptr) - 1, np.inf)
    d[source] = 0.0
    while True:
        new = np.minimum(d, row_reduce(np.minimum, d, indptr, indices,
                                       np.inf) + 1.0)
        if np.array_equal(new, d):
            return d
        d = new


def pagerank_reference(indptr, indices, damping: float, steps: int):
    n = len(indptr) - 1
    inv = 1.0 / np.maximum(np.diff(indptr), 1)
    rank = np.full(n, 1.0 / n)
    for _ in range(steps):
        # the arcs into a vertex are its row's arcs reversed
        rank = (1.0 - damping) / n + damping * row_reduce(
            np.add, rank * inv, indptr, indices, 0.0)
    return rank


def pagerank_gap(rank, ref) -> float:
    return float(np.abs(np.asarray(rank, np.float64) - ref).sum()
                 / np.abs(ref).sum())


def wcc_reference(indptr, indices) -> np.ndarray:
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    n = len(indptr) - 1
    graph = csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                       shape=(n, n))
    _, comp = connected_components(graph, directed=True,
                                   connection="strong")
    _, least = np.unique(comp, return_index=True)
    return least[comp]


def _bf16(x) -> np.ndarray:
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def references(ds: dict, job: dict) -> dict:
    """The PageRank and WCC references of one generated graph. SciPy's
    component search releases the interpreter's lock, so it runs in a
    thread of its own beside PageRank's."""
    indptr, indices = ds["indptr"], ds["indices"]
    with ThreadPoolExecutor(1) as pool:
        wcc = pool.submit(wcc_reference, indptr, indices)
        rank = pagerank_reference(indptr, indices, job["damping"],
                                  job["pagerank_steps"])
        return {"pagerank": rank, "wcc": wcc.result()}


def control_answers(ds: dict, refs: dict) -> dict:
    """The references, rounded through bfloat16, as a rotation's answers."""
    return {"bfs": _bf16(bfs_reference(ds["indptr"], ds["indices"],
                                       ds["source"])),
            "pagerank": _bf16(refs["pagerank"]), "wcc": _bf16(refs["wcc"])}


def numbers(ds: dict, refs: dict, answers) -> dict:
    """Each compared number, by its worst rotation."""
    indptr, indices, source = ds["indptr"], ds["indices"], ds["source"]
    return {"bfs_wrong": max(bfs_wrong(a["bfs"], indptr, indices, source)
                             for a in answers),
            "pagerank_gap": max(pagerank_gap(a["pagerank"], refs["pagerank"])
                                for a in answers),
            "wcc_wrong": max(int(np.count_nonzero(a["wcc"] != refs["wcc"]))
                             for a in answers)}


def check(run) -> None:
    run.free_program()
    answers = run.extra.pop("answers")
    t = time.perf_counter()
    refs = references(run.dataset, run.cell.mix)
    run.say(f"references: {time.perf_counter() - t!r} s")
    if run.control:
        answers = [control_answers(run.dataset, refs)] * len(answers)
    got = numbers(run.dataset, refs, answers)
    run.say(f"check of {len(answers)} rotations: "
            f"{time.perf_counter() - t!r} s")
    run.checks += [(name, got[name], LIMITS[name]) for name in LIMITS]
