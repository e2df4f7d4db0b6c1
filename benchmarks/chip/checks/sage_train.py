"""The plain reference of the fused GraphSAGE step, and the check that
decides ``correct``.

The reference follows the program's first three steps from the same
seed on its own: the benchmark's weights drawn from the seed, the
generated graph's CSR arrays as drawn, the same uniform draws per hop
(``fold_in(fold_in(PRNGKey(DRAW_SEED), step), hop)``, column
``floor(u·deg)``), the feature and label gather, a
mean-aggregator GraphSAGE in float32 at ``highest`` matmul precision, its
softmax cross-entropy and plain SGD. It imports nothing of the program.

Compared, each by the worst of its parts: each step's loss; the norm of
the first gradient as SGD got it, ``(w0 - w1) / lr`` per leaf; and the
norm of each leaf's change over the three steps. A leaf's gap is
``|‖program‖ - ‖reference‖|`` over the larger of the reference's norm
of that leaf and of the median leaf. Leaves whose reference gradient is
under a thousandth of the median leaf's move by round-off alone and are
left out of the change.

Control (``run.control``): the reference in bfloat16 stands in for the
program's steps.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.runners.train import DRAW_SEED, init_params, seed_key

# a leaf whose reference gradient norm is under this share of the median
# leaf's moves by round-off alone
STILL_LEAF = 1e-3

# limits of the compared numbers, between the program's readings (lower)
# and the least of the bfloat16 control's and the faults' (upper) on one
# TPU v5e: PERF.md §4
LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 0.1, "change_norm_gap": 0.05}


def _loss(params, feats, nbrs, labels, fanouts):
    k = len(fanouts)
    h = list(feats)
    for l in range(k):
        p = params[f"l{l}"]
        nxt = []
        for depth in range(k - l):
            cur = h[depth]
            nb = h[depth + 1].reshape(cur.shape[0], fanouts[depth], -1)
            valid = (nbrs[depth].reshape(cur.shape[0], -1) >= 0)[..., None]
            valid = valid.astype(cur.dtype)
            mean = jnp.sum(nb * valid, 1) / jnp.maximum(jnp.sum(valid, 1), 1)
            nxt.append(jax.nn.relu(cur @ p["w_self"] + mean @ p["w_nbr"]
                                   + p["b"]))
        h = nxt
    logits = (h[0] @ params["out"]["w"] + params["out"]["b"]).astype(
        jnp.float32)
    gold = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, -1) - gold)


def _step_fn(fanouts, lr, dtype):
    def step(params, tables, key, seeds):
        starts, deg, indices, feat, lab = tables
        n = starts.shape[0]
        frontier = seeds
        fronts, nbrs = [seeds], []
        for l, f in enumerate(fanouts):
            u = jax.random.uniform(jax.random.fold_in(key, l),
                                   (frontier.shape[0], f), jnp.float32)
            ok = (frontier >= 0) & (frontier < n)
            row = jnp.where(ok, frontier, 0)
            d = deg[row][:, None]
            col = jnp.minimum((u * d.astype(jnp.float32)).astype(jnp.int32),
                              jnp.maximum(d - 1, 0))
            nb = jnp.where(ok[:, None] & (d > 0),
                           indices[starts[row][:, None] + col], -1)
            nbrs.append(nb)
            frontier = nb.reshape(-1)
            fronts.append(frontier)
        feats = [feat[jnp.where(fr >= 0, fr, n)].astype(dtype)
                 for fr in fronts]
        labels = lab[seeds]
        params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
        loss, g = jax.value_and_grad(_loss)(params, feats, nbrs, labels,
                                           fanouts)
        new = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), new), \
            loss.astype(jnp.float32)
    return jax.jit(step)


def reference_steps(ds: dict, job: dict, n_classes: int, seed: int,
                    step0: int, dtype=jnp.float32):
    """(losses, params after each step) of the first steps, from scratch."""
    n = ds["n"]
    deg = np.diff(ds["indptr"]).astype(np.int32)
    starts = ds["indptr"][:-1].astype(np.int32)
    indices = np.concatenate([ds["indices"], [-1]]).astype(np.int32)
    feat = np.zeros((n + 1, ds["vprops"]["feat"].shape[1]), np.float32)
    feat[:n] = ds["vprops"]["feat"]
    lab = ds["vprops"]["label"].astype(np.int32)
    tables = tuple(jnp.asarray(x) for x in (starts, deg, indices, feat, lab))
    fanouts = tuple(job["fanouts"])
    dims = (feat.shape[1],) + (job["hidden"],) * len(fanouts)
    base = jax.random.PRNGKey(DRAW_SEED)
    step = _step_fn(fanouts, job["lr"], dtype)
    with jax.default_matmul_precision("highest"):
        params = init_params(seed_key(seed), dims, n_classes)
        snaps = {0: jax.tree_util.tree_map(np.asarray, params)}
        losses = []
        for i in range(job["first_steps"]):
            s = step0 + i
            seeds = np.random.default_rng(s).integers(
                0, n, job["batch_size"]).astype(np.int32)
            params, loss = step(params, tables,
                                jax.random.fold_in(base, np.uint32(s)),
                                jnp.asarray(seeds))
            losses.append(float(loss))
            snaps[i + 1] = jax.tree_util.tree_map(np.asarray, params)
    return losses, snaps


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _norms(a, b):
    return np.array([np.linalg.norm((x - y).astype(np.float64))
                     for x, y in zip(_leaves(a), _leaves(b))])


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst leaf of |‖program‖ - ‖reference‖| / max(‖reference leaf‖,
    ‖median reference leaf‖)."""
    keep = np.ones(len(ref), bool) if keep is None else keep
    floor = np.median(ref[keep])
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    return float(np.max(gaps[keep]))


def compare(prog_losses, prog_snaps, ref_losses, ref_snaps, lr, k):
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog_losses,
                                                        ref_losses))
    g_ref = _norms(ref_snaps[0], ref_snaps[1]) / lr
    g_prog = _norms(prog_snaps[0], prog_snaps[1]) / lr
    moving = g_ref >= STILL_LEAF * np.median(g_ref)
    return {"still_leaves": int(np.sum(~moving)), "loss_gap": loss_gap,
            "grad_norm_gap": leaf_gaps(g_prog, g_ref),
            "change_norm_gap": leaf_gaps(_norms(prog_snaps[0], prog_snaps[k]),
                                         _norms(ref_snaps[0], ref_snaps[k]),
                                         moving)}


def check(run) -> None:
    job = run.cell.mix
    ex = run.extra
    losses, snaps = ex.pop("losses"), ex.pop("snaps")
    run.free_program()
    t = time.perf_counter()
    n_classes = run.cell.config["graph"]["n_classes"]
    ref_losses, ref_snaps = reference_steps(run.dataset, job, n_classes,
                                            run.seed, ex["step0"])
    if run.control:
        losses, snaps = reference_steps(run.dataset, job, n_classes,
                                        run.seed, ex["step0"], jnp.bfloat16)
    gaps = compare(losses, snaps, ref_losses, ref_snaps, job["lr"],
                   job["first_steps"])
    run.say(f"losses: program {losses!r}, reference {ref_losses!r}; "
            f"reference {time.perf_counter() - t!r} s; leaves left out of "
            f"the change: {gaps['still_leaves']}")
    run.checks += [(name, gaps[name], LIMITS[name]) for name in LIMITS]
