"""Jitted public wrappers around the Pallas kernels.

Each op auto-selects interpret mode on CPU (the container target) and falls
back to the jnp oracle where a kernel precondition fails (e.g. unsorted
segments). The TPU path is exercised structurally: the same pallas_call
lowers for the TPU target in the dry-run's kernel-lowering check.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.kernels import segment_sum as ss
from repro.kernels import spmv as sp
from repro.storage.partition import PAD_SENTINEL


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ flash attention
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Multi-head attention via the Pallas kernel.

    q:[B,S,H,D], k/v:[B,T,K,D] (GQA broadcast handled here).
    Returns [B,S,H,D]."""
    interpret = _default_interpret() if interpret is None else interpret
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    if S % block_q or T % block_kv:
        return _attention_fallback(q, k, v, causal, window, scale)

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, K, G, T, D)).reshape(B * H, T, D)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, K, G, T, D)).reshape(B * H, T, D)
    out = fa.flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                                  scale=scale, block_q=block_q,
                                  block_kv=block_kv, interpret=interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _attention_fallback(q, k, v, causal, window, scale):
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, K, G, k.shape[1], D)).reshape(B * H, k.shape[1], D)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, K, G, v.shape[1], D)).reshape(B * H, v.shape[1], D)
    out = ref.attention_ref(qf, kf, vf, causal=causal, window=window,
                            scale=scale)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------- spmv
def csr_to_ell(indptr: np.ndarray, indices: np.ndarray,
               weights: Optional[np.ndarray] = None,
               row_split: int = 1024) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """CSR → padded ELL slab (host-side, done once per graph).

    Heavy rows (> row_split) are split into multiple slab rows; returns
    (ell_idx [N',W], ell_w [N',W], row_map [N'] — slab row → original row).
    """
    n = len(indptr) - 1
    deg = np.diff(indptr)
    if weights is None:
        weights = np.ones(len(indices), np.float32)
    rows = []
    for r in range(n):
        lo, hi = int(indptr[r]), int(indptr[r + 1])
        for s in range(lo, hi, row_split):
            rows.append((r, s, min(hi, s + row_split)))
    if not rows:
        rows = [(0, 0, 0)]
    W = max(1, max(hi - lo for _, lo, hi in rows))
    W = -(-W // 128) * 128 if W > 128 else W      # lane alignment
    Np = -(-len(rows) // 256) * 256               # block_rows alignment
    ell_idx = np.full((Np, W), PAD_SENTINEL, np.int32)
    ell_w = np.zeros((Np, W), np.float32)
    row_map = np.zeros(Np, np.int64)
    for i, (r, lo, hi) in enumerate(rows):
        ell_idx[i, : hi - lo] = indices[lo:hi]
        ell_w[i, : hi - lo] = weights[lo:hi]
        row_map[i] = r
    return ell_idx, ell_w, row_map


def spmv(ell_idx: jnp.ndarray, ell_w: jnp.ndarray, x: jnp.ndarray,
         row_map: jnp.ndarray, n_rows: int,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = A @ x over the ELL slab; slab rows are reduced back onto original
    rows (split-row support) with a final scatter-add."""
    interpret = _default_interpret() if interpret is None else interpret
    y_slab = sp.spmv_ell(ell_idx, ell_w, x, interpret=interpret)
    return jnp.zeros((n_rows,), jnp.float32).at[row_map].add(y_slab)


# ------------------------------------------------------------ frontier hop
def frontier_step(ell_idx: jnp.ndarray, ell_w: jnp.ndarray, x: jnp.ndarray,
                  row_map: jnp.ndarray, n_rows: int,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """One batched EXPAND hop: Y [B, n_rows] = X [B, N] pushed through the
    pull-ELL slab (``csr_to_ell`` of the hop's *reverse* adjacency), slab
    rows reduced back onto destination vertices with a scatter-add."""
    interpret = _default_interpret() if interpret is None else interpret
    from repro.kernels import frontier as fr
    y_slab = fr.frontier_ell(ell_idx, ell_w, x, interpret=interpret)
    B = x.shape[0]
    return jnp.zeros((B, n_rows), jnp.float32).at[:, row_map].add(y_slab)


def frontier_minplus_step(ell_idx: jnp.ndarray, ell_w: jnp.ndarray,
                          x: jnp.ndarray, row_map: jnp.ndarray, n_rows: int,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """One batched min-plus (shortest-path) relaxation: Y [B, n_rows] =
    X [B, N] distances pulled through the ELL slab in the tropical
    semiring; slab rows reduce back onto destination vertices with a
    scatter-min (split heavy rows take the min of their parts)."""
    interpret = _default_interpret() if interpret is None else interpret
    from repro.kernels import frontier as fr
    y_slab = fr.frontier_ell_minplus(ell_idx, ell_w, x, interpret=interpret)
    B = x.shape[0]
    return jnp.full((B, n_rows), jnp.inf,
                    jnp.float32).at[:, row_map].min(y_slab)


# -------------------------------------------------------------- segment sum
def segment_sum(vals: jnp.ndarray, segs: jnp.ndarray, n_out: int, *,
                interpret: Optional[bool] = None,
                window: int = 1024, block_e: int = 512) -> jnp.ndarray:
    """Sorted-segment sum via the Pallas kernel; falls back to jnp
    scatter-add when preconditions don't hold (unsorted / wide spans)."""
    interpret = _default_interpret() if interpret is None else interpret
    E = vals.shape[0]
    pad = (-E) % block_e
    if pad:
        vals = jnp.concatenate([vals, jnp.zeros((pad,), vals.dtype)])
        segs = jnp.concatenate([segs, jnp.full((pad,), -1, segs.dtype)])
    # one spare window: a tile's window opens at its first segment rounded
    # down to 128, so it may reach up to ``window`` rows past n_out
    n_pad = (-(-n_out // window) + 1) * window
    # precondition check is host-side metadata in the engine; here assume
    # sorted inputs (CSC order) — violations are the caller's fallback.
    out = ss.segment_sum_sorted(vals, segs.astype(jnp.int32), n_pad,
                                block_e=block_e, window=window,
                                interpret=interpret)
    return out[:n_out]


def tail_reduce(x: jnp.ndarray, vals: jnp.ndarray, *,
                interpret: Optional[bool] = None,
                block_n: int = 512):
    """Masked per-row reductions for the device tail (DESIGN.md §14):
    ``x`` [B, N] float32 path counts (0 ⇒ vertex absent from the row's
    multiset), ``vals`` [C, N] float32 aggregate value vectors. Returns
    ``(cnt [B], sums [B, C], sabs [B, C], mins [B, C], maxs [B, C])`` —
    COUNT(*), weighted SUMs, their absolute-value twins (the float32
    exactness certificate), and masked MIN/MAX (±inf on empty rows).
    Zero-padded lanes are inert by construction."""
    interpret = _default_interpret() if interpret is None else interpret
    b, n = x.shape
    c = vals.shape[0]
    if c == 0 or b == 0:
        return ref.tail_reduce_jnp(x, vals)
    pad = (-n) % block_n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((b, pad), x.dtype)], axis=1)
        vals = jnp.concatenate(
            [vals, jnp.zeros((c, pad), vals.dtype)], axis=1)
    from repro.kernels import reduce as rd
    cnt, sums, sabs, mins, maxs = rd.tail_reduce_grid(
        x, vals, block_n=min(block_n, x.shape[1]), interpret=interpret)
    return cnt[:, 0], sums, sabs, mins, maxs


def masked_order(key: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Stable ascending argsort of ``key`` restricted to ``mask`` lanes:
    masked-out entries take a +inf key and sort last, so the first
    ``mask.sum()`` indices are the result in ascending key order (ties in
    lane order — the interpreter's stable-sort tie order; the host
    reverses that slice for DESC, matching its reversed stable sort)."""
    return jnp.argsort(jnp.where(mask, key, jnp.inf), axis=-1, stable=True)


def segment_sum_checked(vals: np.ndarray, segs: np.ndarray, n_out: int,
                        **kw) -> jnp.ndarray:
    """Host-checked version: verifies sortedness + span precondition and
    falls back to the oracle when violated."""
    segs_np = np.asarray(segs)
    block_e = kw.get("block_e", 512)
    window = kw.get("window", 1024)
    ok = bool(np.all(np.diff(segs_np[segs_np >= 0]) >= 0))
    if ok:
        E = len(segs_np)
        for t0 in range(0, E, block_e):
            tile = segs_np[t0:t0 + block_e]
            tile = tile[tile >= 0]
            if len(tile) == 0:
                continue
            lo = (tile.min() // 128) * 128
            if tile.max() >= lo + window:
                ok = False
                break
    if not ok:
        return ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(segs), n_out)
    return segment_sum(jnp.asarray(vals), jnp.asarray(segs), n_out, **kw)
