"""The plain reference of the fused R-GCN step, and the check that decides
``correct``.

The reference follows the program's first three steps from the same seed
on its own: the benchmark's weights drawn from the seed, the generated
graph's arrays as drawn, the seeds ``default_rng(step)`` over the papers'
id range, the same uniform draws per hop (``fold_in(fold_in(PRNGKey(
DRAW_SEED), step), hop)``, column ``floor(u·deg)``) reading each arc's
neighbour and relation, every frontier's node types gathered from the
vertex types, and a relational GCN written relation by relation and type
by type in float32 at ``highest`` matmul precision: per relation the mean
of its draws times W_r, plus each target's root weight and bias of its
node type, ReLU between the layers. Its softmax cross-entropy is
differentiated densely with respect to the whole input table, and SGD
moves the weights and the rows of the embedding types. It imports nothing
of the program.

Compared, each by the worst of its parts: each step's loss; the norm of
the first gradient and of each weight's change over the three steps, as
``checks/sage_train.py`` compares them; ``embed_change_gap``, a learned
node type's |‖program's change‖ − ‖reference's change‖| of its rows over
the three steps, over the reference's; and ``paper_rows_moved``, the rows
of the other types (and the table's padding row) whose bits the program
changed, exactly 0.

Control (``run.control``): the reference in bfloat16 stands in for the
program's steps, its weights and input table held in bfloat16 from one
step to the next. A cast to bfloat16 and back inside one jitted step is
not enough: XLA on the TPU may keep the excess precision and drop the
round trip, so the state is rounded where it leaves the step.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.checks.sage_train import compare
from benchmarks.chip.harness import seed_key
from benchmarks.chip.runners.rgcn_train import (DRAW_SEED, init_params,
                                                model_dims, n_relations,
                                                type_ids)

# limits of the compared numbers, between the program's readings (lower)
# and the least of the bfloat16 control's and the faults' (upper) on one
# TPU v5e: PERF.md §4
LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 0.1, "change_norm_gap": 0.05,
          "embed_change_gap": 0.05, "paper_rows_moved": 0}


def _conv(p, cur, nbr, rel, types):
    out = 0.0
    for r in range(p["w_rel"].shape[0]):
        hit = (rel == r).astype(cur.dtype)
        mean = jnp.sum(nbr * hit[..., None], 1) / jnp.maximum(
            jnp.sum(hit, 1), 1)[:, None]
        out = out + mean @ p["w_rel"][r]
    for t in range(p["w_root"].shape[0]):
        own = cur @ p["w_root"][t] + p["b"][t]
        out = out + jnp.where((types == t)[:, None], own, 0)
    return out


def _loss(params, table, fronts, rels, types, labels, fanouts):
    k = len(fanouts)
    h = [jnp.where((f >= 0)[:, None], table[jnp.maximum(f, 0)], 0)
         for f in fronts]
    for l in range(k):
        h = [_conv(params[f"l{l}"], h[d],
                   h[d + 1].reshape(h[d].shape[0], fanouts[d], -1),
                   rels[d], types[d]) for d in range(k - l)]
        if l < k - 1:
            h = [jax.nn.relu(x) for x in h]
    logits = h[0].astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, -1) - gold)


def _step_fn(fanouts, lr, dtype):
    def step(params, table, learned, tables, key, seeds):
        starts, deg, indices, relations, vtypes, lab = tables
        n = starts.shape[0]
        frontier, fronts, rels = seeds, [seeds], []
        for l, f in enumerate(fanouts):
            u = jax.random.uniform(jax.random.fold_in(key, l),
                                   (frontier.shape[0], f), jnp.float32)
            ok = (frontier >= 0) & (frontier < n)
            row = jnp.where(ok, frontier, 0)
            d = deg[row][:, None]
            col = jnp.minimum((u * d.astype(jnp.float32)).astype(jnp.int32),
                              jnp.maximum(d - 1, 0))
            pos = starts[row][:, None] + col
            valid = ok[:, None] & (d > 0)
            frontier = jnp.where(valid, indices[pos], -1).reshape(-1)
            rels.append(jnp.where(valid, relations[pos], -1))
            fronts.append(frontier)
        types = [vtypes[jnp.maximum(fr, 0)] for fr in fronts]
        loss, (g, g_table) = jax.value_and_grad(_loss, (0, 1))(
            params, table, fronts, rels, types, lab[seeds], fanouts)
        params = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)
        table = table - lr * jnp.where(learned[:, None], g_table, 0)
        return params, table, loss.astype(jnp.float32)
    return jax.jit(step)


def _host(tree):
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x).astype(np.float32, copy=False), tree)


def learned_rows(ds: dict, cfg: dict) -> np.ndarray:
    return np.isin(ds["types"], type_ids(cfg)[1])


def reference_steps(ds: dict, cfg: dict, job: dict, seed: int, step0: int,
                    dtype=jnp.float32):
    """(losses, weights after each step, input table after the last) of
    the first steps, from scratch."""
    n = ds["n"]
    deg = np.diff(ds["indptr"]).astype(np.int32)
    starts = ds["indptr"][:-1].astype(np.int32)
    pad = lambda a: np.concatenate([a, [-1]]).astype(np.int32)
    tables = tuple(jnp.asarray(x) for x in (
        starts, deg, pad(ds["indices"]), pad(ds["relations"]), ds["types"],
        ds["vprops"]["label"].astype(np.int32)))
    learned = jnp.asarray(learned_rows(ds, cfg))
    fanouts = tuple(job["fanouts"])
    seed_type, _ = type_ids(cfg)
    lo = int(np.flatnonzero(ds["types"] == seed_type)[0])
    hi = lo + int(ds["counts"][cfg["learn"]["seed_type"]])
    base = jax.random.PRNGKey(DRAW_SEED)
    step = _step_fn(fanouts, job["lr"], dtype)
    with jax.default_matmul_precision("highest"):
        params = init_params(seed_key(seed), model_dims(cfg, job),
                             n_relations(cfg), len(ds["counts"]))
        snaps = {0: _host(params)}
        # the state in ``dtype`` between steps (the control's rounding)
        params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
        table = jnp.asarray(ds["vprops"]["feat"]).astype(dtype)
        losses = []
        for i in range(job["first_steps"]):
            s = step0 + i
            seeds = np.random.default_rng(s).integers(
                lo, hi, job["batch_size"]).astype(np.int32)
            params, table, loss = step(
                params, table, learned, tables,
                jax.random.fold_in(base, np.uint32(s)), jnp.asarray(seeds))
            losses.append(float(loss))
            snaps[i + 1] = _host(params)
    return losses, snaps, _host(table)


def embed_gaps(ds: dict, cfg: dict, prog_table: np.ndarray,
               ref_table: np.ndarray) -> dict:
    """``embed_change_gap`` (the worst learned type) and
    ``paper_rows_moved`` of the program's table after the first steps
    (with its padding row) against the reference's."""
    n, table0 = ds["n"], ds["vprops"]["feat"]
    moved = np.any(prog_table[:n] != table0, axis=1)
    fixed = ~learned_rows(ds, cfg)
    gaps = []
    for t in type_ids(cfg)[1]:
        rows = ds["types"] == t
        norm = lambda a: np.linalg.norm((a[rows] - table0[rows]).astype(
            np.float64))
        ref = norm(ref_table)
        gaps.append(abs(norm(prog_table[:n]) - ref) / ref)
    return {"embed_change_gap": float(max(gaps)),
            "paper_rows_moved": int(np.sum(moved & fixed))
            + int(np.any(prog_table[n:] != 0))}


def check(run) -> None:
    job, cfg, ds = run.cell.mix, run.cell.config, run.dataset
    ex = run.extra
    losses, snaps, table = ex.pop("losses"), ex.pop("snaps"), ex.pop("table")
    run.free_program()
    t = time.perf_counter()
    ref_losses, ref_snaps, ref_table = reference_steps(ds, cfg, job,
                                                       run.seed, ex["step0"])
    if run.control:
        losses, snaps, table = reference_steps(ds, cfg, job, run.seed,
                                               ex["step0"], jnp.bfloat16)
        table = np.concatenate([table, np.zeros_like(table[:1])])
    gaps = compare(losses, snaps, ref_losses, ref_snaps, job["lr"],
                   job["first_steps"])
    gaps.update(embed_gaps(ds, cfg, table, ref_table))
    run.say(f"losses: program {losses!r}, reference {ref_losses!r}; "
            f"reference {time.perf_counter() - t!r} s; leaves left out of "
            f"the change: {gaps['still_leaves']}")
    run.checks += [(name, gaps[name], LIMITS[name]) for name in LIMITS]
