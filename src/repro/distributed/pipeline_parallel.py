"""Pipeline parallelism over the slow (cross-pod) mesh axis.

At 2+ pods the baseline DP-across-pods pays a full-gradient all-reduce over
the inter-pod links every step. GPipe-style pipelining moves only microbatch
*activations* across pods — the §Perf collective-term hillclimb (see
EXPERIMENTS.md). Implementation: ``shard_map`` over the ``pod`` axis, stage
parameters sharded by their leading stage dim, microbatch activations
rotated with ``jax.lax.ppermute`` each tick; fully differentiable (ppermute
transposes to the reverse permutation, so ``jax.grad`` yields the 1F1B-
equivalent dataflow with GPipe scheduling).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def gpipe_loss(stage_fn: Callable, loss_fn: Callable,
               stage_params: Any, x_micro: jnp.ndarray,
               y_micro: jnp.ndarray, *, mesh: Mesh, axis: str = "pod"):
    """Pipelined loss over ``n_stages = mesh.shape[axis]`` stages.

    stage_fn(params_stage, h) -> h      (one stage's layers)
    loss_fn(h, y) -> scalar             (applied on the LAST stage)
    stage_params: leaves [n_stages, ...] (sharded over ``axis``)
    x_micro:      [n_micro, mb, ...]    (replicated microbatch inputs)
    y_micro:      [n_micro, mb]         (labels)
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    other_axes = [a for a in mesh.axis_names if a != axis]

    def spmd(params, xs, ys):
        # params leaves arrive as [1, ...] local stage slices
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        sid = jax.lax.axis_index(axis)
        T = n_micro + n_stages - 1
        h = jnp.zeros(xs.shape[1:], xs.dtype)
        loss_sum = jnp.zeros((), jnp.float32)
        n_done = jnp.zeros((), jnp.float32)
        for t in range(T):
            # stage 0 injects microbatch t; others take the rotated input
            inject = xs[min(t, n_micro - 1)]
            use_inject = (sid == 0) & (t < n_micro)
            h_in = jnp.where(use_inject, inject, h)
            h_out = stage_fn(params, h_in)
            # last stage consumes microbatch (t - n_stages + 1)
            micro_id = t - (n_stages - 1)
            is_last = sid == n_stages - 1
            valid = is_last & (micro_id >= 0) & (micro_id < n_micro)
            y = ys[jnp.clip(micro_id, 0, n_micro - 1)]
            l = loss_fn(h_out, y)
            loss_sum = loss_sum + jnp.where(valid, l, 0.0)
            n_done = n_done + jnp.where(valid, 1.0, 0.0)
            h = jax.lax.ppermute(h_out, axis, fwd_perm)
        # average over microbatches, summed across stages (only last
        # contributes) then broadcast
        total = jax.lax.psum(loss_sum, axis)
        count = jax.lax.psum(n_done, axis)
        return total / jnp.maximum(count, 1.0)

    pspec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(pspec_params, P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_micro, y_micro)


def make_pp_train_step(stage_fn: Callable, loss_fn: Callable, *,
                       mesh: Mesh, axis: str = "pod", lr: float = 1e-3):
    """SGD train step over the pipelined loss (used by the hillclimb cell
    and the subprocess correctness test)."""

    def step(stage_params, x_micro, y_micro):
        def l(p):
            return gpipe_loss(stage_fn, loss_fn, p, x_micro, y_micro,
                              mesh=mesh, axis=axis)

        loss, grads = jax.value_and_grad(l)(stage_params)
        new = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32) - lr * g).astype(p.dtype),
            stage_params, grads)
        return new, loss

    return step
