"""Pallas kernel sweeps: shapes × dtypes vs the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_bhsd

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("S,T,D,bq,bkv", [
        (128, 128, 64, 64, 64),
        (256, 256, 128, 128, 128),
        (128, 384, 64, 64, 128),     # cross lengths
    ])
    def test_causal_matches_ref(self, S, T, D, bq, bkv, dtype):
        q = _rand((3, S, D), dtype)
        k = _rand((3, T, D), dtype)
        v = _rand((3, T, D), dtype)
        out = flash_attention_bhsd(q, k, v, causal=True, block_q=bq,
                                   block_kv=bkv, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_noncausal(self):
        q, k, v = (_rand((2, 128, 64), jnp.float32) for _ in range(3))
        out = flash_attention_bhsd(q, k, v, causal=False, block_q=64,
                                   block_kv=64, interpret=True)
        want = ref.attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [32, 64, 100])
    def test_sliding_window(self, window):
        q, k, v = (_rand((2, 256, 64), jnp.float32) for _ in range(3))
        out = flash_attention_bhsd(q, k, v, causal=True, window=window,
                                   block_q=64, block_kv=64, interpret=True)
        want = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    def test_gqa_wrapper(self):
        q = _rand((2, 128, 8, 64), jnp.float32)
        k = _rand((2, 128, 2, 64), jnp.float32)
        v = _rand((2, 128, 2, 64), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, block_q=64,
                                  block_kv=64, interpret=True)
        want = ops._attention_fallback(q, k, v, True, None, 1 / 8.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)


class TestSpMV:
    @pytest.mark.parametrize("N,W", [(256, 8), (512, 16), (1024, 33)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, N, W, dtype):
        idx = RNG.integers(0, N, (N, W)).astype(np.int32)
        idx[RNG.random((N, W)) < 0.4] = -1
        w = _rand((N, W), dtype)
        x = _rand((N,), jnp.float32)
        out = ops.spmv(jnp.asarray(idx), w, x, jnp.arange(N), N,
                       interpret=True)
        want = ref.spmv_ref(jnp.asarray(idx), w, x)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=tol, atol=tol)

    def test_csr_to_ell_split_rows(self):
        # a power-law row gets split, results still exact
        indptr = np.array([0, 5000, 5002, 5004])
        indices = RNG.integers(0, 3, 5004).astype(np.int32)
        weights = RNG.standard_normal(5004).astype(np.float32)
        ell_i, ell_w, rmap = ops.csr_to_ell(indptr, indices, weights,
                                            row_split=1024)
        assert ell_i.shape[1] <= 1024
        x = jnp.asarray(RNG.standard_normal(3).astype(np.float32))
        y = ops.spmv(jnp.asarray(ell_i), jnp.asarray(ell_w), x,
                     jnp.asarray(rmap), 3, interpret=True)
        # dense reference
        dense = np.zeros((3, 3), np.float32)
        for r in range(3):
            for e in range(indptr[r], indptr[r + 1]):
                dense[r, indices[e]] += weights[e]
        want = dense @ np.asarray(x)
        np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-4)


class TestSegmentSum:
    @pytest.mark.parametrize("E,N", [(512, 256), (2048, 900), (4096, 4096)])
    def test_sorted_matches_ref(self, E, N):
        segs = np.sort(RNG.integers(0, N, E)).astype(np.int32)
        vals = RNG.standard_normal(E).astype(np.float32)
        out = ops.segment_sum_checked(vals, segs, N, window=8192
                                      if N > 1024 else 1024)
        want = ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(segs), N)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_unsorted_falls_back(self):
        segs = RNG.integers(0, 100, 512).astype(np.int32)   # unsorted
        vals = RNG.standard_normal(512).astype(np.float32)
        out = ops.segment_sum_checked(vals, segs, 100)
        want = ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(segs), 100)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_padding_dropped(self):
        segs = np.concatenate([np.sort(RNG.integers(0, 50, 200)),
                               np.full(56, -1)]).astype(np.int32)
        vals = RNG.standard_normal(256).astype(np.float32)
        out = ops.segment_sum_checked(vals, segs, 50)
        want = ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(segs), 50)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

