"""Pregel — "think like a vertex" programming model over GRAPE (paper §6).

A :class:`VertexProgram` defines per-vertex state, the value each vertex
sends along its out-edges, and the state update from combined incoming
messages. The driver runs synchronized supersteps with a single combined
collective per step (GRAPE's compact-buffer exchange).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.engines.grape.engine import GrapeEngine


@dataclasses.dataclass
class VertexProgram:
    """send(state, degree) -> per-vertex emitted value (broadcast on edges);
    update(state, msgs, step) -> new state; both on dense [N] arrays."""

    init: Callable[[int], Dict[str, jnp.ndarray]]
    send: Callable[[Dict[str, jnp.ndarray], jnp.ndarray], jnp.ndarray]
    update: Callable[[Dict[str, jnp.ndarray], jnp.ndarray, int],
                     Dict[str, jnp.ndarray]]
    combiner: str = "sum"
    use_weights: bool = False
    # convergence: L1 residual on this state key (None = fixed steps)
    residual_key: Optional[str] = None
    tol: float = 1e-6


def run_pregel(engine: GrapeEngine, prog: VertexProgram, max_steps: int,
               jit: bool = True, cache_key=None,
               init_state: Optional[Dict[str, jnp.ndarray]] = None
               ) -> Dict[str, jnp.ndarray]:
    """``init_state`` warm-starts the fixpoint from a previous solution
    instead of ``prog.init`` (DESIGN.md §15): sound when every state key's
    update is a contraction (pagerank — converges to the same fixpoint
    tolerance) or monotone min-propagation started from a valid upper
    bound (bfs/sssp/wcc on an append-only graph — the fixpoint is unique
    and reached bit-exactly). The caller owns that contract; the jitted
    fixpoint itself is identical either way."""
    n = engine.frags.n_vertices
    state = prog.init(n) if init_state is None else \
        {k: jnp.asarray(v) for k, v in init_state.items()}

    def one_step(state, step, frags):
        deg = frags.out_degree.astype(jnp.float32)
        emitted = prog.send(state, deg)                 # [N]
        owned = engine.owned_view(emitted)              # [F, v_per]
        msgs = engine.superstep(owned, prog.combiner, prog.use_weights,
                                frags)
        return prog.update(state, msgs, step)

    if not jit:
        for step in range(max_steps):
            new_state = one_step(state, jnp.asarray(step, jnp.int32),
                                 engine.frags)
            if prog.residual_key is not None:
                res = float(jnp.sum(jnp.abs(
                    new_state[prog.residual_key] - state[prog.residual_key])))
                state = new_state
                if res <= prog.tol:
                    break
            else:
                state = new_state
        return state

    # jitted fixpoint: the whole superstep loop is ONE device program
    # (lax.while_loop with the residual convergence check on device) —
    # GRAPE's tight loop, no per-superstep host dispatch. The fragment
    # arrays are an argument, not closed over: constants of the edge
    # list's size would bake into the program and stall its compile.
    def fixpoint(state, frags):
        def cond(carry):
            _, step, res = carry
            return (step < max_steps) & (res > prog.tol)

        def body(carry):
            st, step, _ = carry
            new = one_step(st, step, frags)
            if prog.residual_key is not None:
                diff = jnp.abs(new[prog.residual_key]
                               - st[prog.residual_key])
                # inf-inf (still-unreached vertices) = NaN → no change;
                # inf-finite (newly reached) → treat as change
                diff = jnp.nan_to_num(diff, nan=0.0, posinf=1e30)
                res = jnp.sum(diff)
            else:
                res = jnp.float32(jnp.inf)
            return new, step + 1, res

        out, _, _ = jax.lax.while_loop(
            cond, body, (state, jnp.zeros((), jnp.int32),
                         jnp.float32(jnp.inf)))
        return out

    if cache_key is not None:
        cache = engine.__dict__.setdefault("_pregel_jit_cache", {})
        fx = cache.get(cache_key)
        if fx is None:
            fx = jax.jit(fixpoint)
            cache[cache_key] = fx
    else:
        fx = jax.jit(fixpoint)
    return fx(state, engine.frags)
