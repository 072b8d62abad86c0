"""Paged decode attention kernel vs dense reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops.attention import attention_with_cache
from cyberfabric_core_tpu.ops.paged_attention import (
    paged_decode_attention, paged_gather_dense)


def _build_pool(key, B, lengths, page, Pmax, Hkv, D, N):
    """Random one-layer pool as the engine keeps it ([1, N, page, Hkv*D]) +
    per-slot page tables with distinct physical pages."""
    kk, kv = jax.random.split(key)
    k_pool = jax.random.normal(kk, (1, N, page, Hkv * D), jnp.float32)
    v_pool = jax.random.normal(kv, (1, N, page, Hkv * D), jnp.float32)
    rng = np.random.default_rng(0)
    # shuffled distinct page ids so table order != physical order
    ids = rng.permutation(N - 1)[: B * Pmax] + 1
    pt = ids.reshape(B, Pmax).astype(np.int32)
    return k_pool, v_pool, jnp.asarray(pt)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,Pmax,lengths,window", [
    (2, 4, 2, 32, 16, 4, [33, 7], None),       # GQA, ragged lengths
    (1, 8, 8, 16, 8, 8, [64], None),           # MHA, full pages
    (3, 4, 1, 16, 16, 4, [1, 17, 48], None),   # extreme GQA, tiny lengths
    (2, 4, 2, 32, 16, 4, [60, 29], 24),        # sliding window
    (2, 32, 8, 128, 16, 4, [33, 7], None),     # mistral-7b's heads
    (2, 32, 8, 128, 16, 4, [60, 29], 24),
    (2, 28, 4, 128, 16, 4, [33, 7], None),     # qwen2-7b's: 7 queries a kv head
    (2, 28, 4, 128, 16, 4, [60, 29], 24),
    (2, 8, 8, 96, 16, 4, [33, 7], None),       # phi-3-mini's head size
    (2, 8, 8, 96, 16, 4, [60, 29], 24),
])
def test_paged_matches_dense(B, Hq, Hkv, D, page, Pmax, lengths, window):
    N = B * Pmax + 2
    key = jax.random.PRNGKey(0)
    kq, kp = jax.random.split(key)
    q = jax.random.normal(kq, (B, Hq, D), jnp.float32)
    k_pool, v_pool, pt = _build_pool(kp, B, lengths, page, Pmax, Hkv, D, N)
    lens = jnp.asarray(lengths, jnp.int32)

    out = paged_decode_attention(q, k_pool, v_pool, pt, lens,
                                 interpret=True, sliding_window=window)

    # dense reference: gather pages, then standard attention at q_pos = len-1
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, D)
    q_pos = (lens - 1)[:, None]
    ref = attention_with_cache(q[:, None], k_dense, v_dense, q_pos, lens,
                               sliding_window=window)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_shared_pages():
    """Two slots referencing the SAME physical prefix pages (prefix cache hit)
    must each attend to that shared history correctly."""
    B, Hq, Hkv, D, page, Pmax = 2, 4, 2, 16, 8, 4
    N = 16
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, D), jnp.float32)
    k_pool = jax.random.normal(kk, (1, N, page, Hkv * D), jnp.float32)
    v_pool = jax.random.normal(kv, (1, N, page, Hkv * D), jnp.float32)
    # both slots share pages [3, 7] as prefix; private tails differ
    pt = jnp.asarray([[3, 7, 2, 0], [3, 7, 9, 0]], jnp.int32)
    lens = jnp.asarray([20, 23], jnp.int32)

    out = paged_decode_attention(q, k_pool, v_pool, pt, lens, interpret=True)
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, D)
    ref = attention_with_cache(q[:, None], k_dense, v_dense,
                               (lens - 1)[:, None], lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
