"""Pallas TPU tiled segment-sum over *sorted* segment ids.

GRAPE's message combining: contributions arrive sorted by destination (CSC
order); each tile of E values is reduced into a 128-aligned window of the
output via a within-tile one-hot matmul (MXU-friendly), then accumulated
into the VMEM-resident output across the sequential grid.

Constraint: one tile's segment ids must span < ``window`` rows (power-law
tails are split by the ops wrapper; violations fall back to jnp scatter-add).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segsum_kernel(vals_ref, segs_ref, y_ref, *, window: int, block_e: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    vals = vals_ref[...].astype(jnp.float32)     # [block_e]
    segs = segs_ref[...]                         # [block_e] int32, sorted
    lo = jnp.min(jnp.where(segs >= 0, segs, 2 ** 30))
    # an all-padding tile adds zeros: park its window at row 0
    win_start = jnp.where(lo == 2 ** 30, 0, (lo // 128) * 128)
    local = segs - win_start
    oh = (jax.lax.broadcasted_iota(jnp.int32, (block_e, window), 1)
          == local[:, None])
    oh = oh & (segs >= 0)[:, None]
    partial = jax.lax.dot_general(
        vals[None, :], oh.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[0]   # [window]
    y_ref[pl.ds(win_start, window)] += partial


def segment_sum_sorted(vals: jnp.ndarray, segs: jnp.ndarray, n_out: int, *,
                       block_e: int = 512, window: int = 1024,
                       interpret: bool = False) -> jnp.ndarray:
    """vals [E] fp, segs [E] int32 sorted ascending (−1 ⇒ dropped), padded to
    a multiple of ``block_e``; output [n_out_padded] fp32 where the caller
    (ops wrapper) pads n_out one window past the last segment, so a window
    opened at any segment stays in bounds."""
    E = vals.shape[0]
    assert E % block_e == 0, (E, block_e)
    assert n_out % window == 0, (n_out, window)
    grid = (E // block_e,)
    return pl.pallas_call(
        functools.partial(_segsum_kernel, window=window, block_e=block_e),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e,), lambda t: (t,)),
            pl.BlockSpec((block_e,), lambda t: (t,)),
        ],
        out_specs=pl.BlockSpec((n_out,), lambda t: (0,)),
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.float32),
        interpret=interpret,
    )(vals, segs)
