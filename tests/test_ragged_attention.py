"""Ragged mixed-batch paged attention kernel vs dense reference.

The kernel contract (ops/paged_attention.ragged_paged_attention): each batch
row attends a variable-length query span (q_start implicit at ``hist``,
length ``q_len``) over its paged KV chain, causally masked relative to its
OWN history — decode rows (q_len=1), chunked-prefill rows (q_len=chunk) and
idle rows (q_len=0) share one dispatch. Golden checks run in interpret mode
on CPU against the dense attention reference; the q_len=1 case must be
BIT-identical to the decode kernel (mixed rounds and pure-decode rounds
must never disagree on a decode row's token).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops.attention import attention_with_cache
from cyberfabric_core_tpu.ops.paged_attention import (
    decode_work_list, paged_decode_attention, paged_gather_dense,
    ragged_paged_attention)


def _build_pool(key, B, page, Pmax, Hkv, D, N):
    """A one-layer pool as the engine keeps it: [1, N, page, Hkv*D]."""
    kk, kv = jax.random.split(key)
    k_pool = jax.random.normal(kk, (1, N, page, Hkv * D), jnp.float32)
    v_pool = jax.random.normal(kv, (1, N, page, Hkv * D), jnp.float32)
    rng = np.random.default_rng(0)
    ids = rng.permutation(N - 1)[: B * Pmax] + 1
    pt = ids.reshape(B, Pmax).astype(np.int32)
    return k_pool, v_pool, jnp.asarray(pt)


def _ref_rows(q, k_pool, v_pool, pt, hist, q_lens, window=None):
    """Dense reference: per row, gather the chain and attend the span at its
    absolute positions."""
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, q.shape[-1])
    outs = []
    for b in range(q.shape[0]):
        ql, h = int(q_lens[b]), int(hist[b])
        if ql == 0:
            outs.append(np.zeros_like(np.asarray(q[b])))
            continue
        pos = jnp.asarray([[h + i for i in range(ql)]], jnp.int32)
        ref = attention_with_cache(
            q[b:b + 1, :ql], k_dense[b:b + 1], v_dense[b:b + 1], pos,
            jnp.asarray([h + ql], jnp.int32), sliding_window=window)
        out = np.zeros_like(np.asarray(q[b]))
        out[:ql] = np.asarray(ref[0])
        outs.append(out)
    return np.stack(outs)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,Pmax,hist,q_lens,window", [
    # pure decode rows (q_len=1) with ragged histories
    (3, 4, 2, 16, 16, 4, [0, 17, 48], [1, 1, 1], None),
    # mixed: decode + chunk spanning a page boundary + idle row
    (3, 4, 2, 16, 16, 6, [37, 12, 0], [1, 23, 0], None),
    # chunk starting exactly ON a page boundary, MHA
    (2, 4, 4, 16, 8, 8, [16, 8], [16, 9], None),
    # cold prefill from zero history (whole span is its own history)
    (2, 4, 1, 16, 16, 4, [0, 0], [20, 5], None),
    # sliding window across a mixed batch
    (3, 4, 2, 16, 16, 6, [40, 10, 25], [1, 14, 2], 24),
    # span longer than one q_block (exercises multiple q-block programs)
    (1, 2, 2, 16, 8, 8, [11, ], [33, ], None),
    # the served models' heads (mistral-7b, qwen2-7b) and phi-3-mini's head
    # size, full attention and a sliding window
    (3, 32, 8, 128, 16, 6, [37, 12, 0], [1, 23, 0], None),
    (3, 32, 8, 128, 16, 6, [40, 10, 25], [1, 14, 2], 24),
    (3, 28, 4, 128, 16, 6, [37, 12, 0], [1, 23, 0], None),
    (3, 28, 4, 128, 16, 6, [40, 10, 25], [1, 14, 2], 24),
    (3, 8, 8, 96, 16, 6, [37, 12, 0], [1, 23, 0], None),
    (3, 8, 8, 96, 16, 6, [40, 10, 25], [1, 14, 2], 24),
])
def test_ragged_matches_dense(B, Hq, Hkv, D, page, Pmax, hist, q_lens, window):
    N = B * Pmax + 2
    key = jax.random.PRNGKey(0)
    kq, kp = jax.random.split(key)
    q_max = -(-max(q_lens) // 8) * 8
    q = jax.random.normal(kq, (B, q_max, Hq, D), jnp.float32)
    k_pool, v_pool, pt = _build_pool(kp, B, page, Pmax, Hkv, D, N)
    hist_a = jnp.asarray(hist, jnp.int32)
    qlen_a = jnp.asarray(q_lens, jnp.int32)

    out = ragged_paged_attention(q, k_pool, v_pool, pt, hist_a, qlen_a,
                                 interpret=True, sliding_window=window)
    ref = _ref_rows(q, k_pool, v_pool, pt, hist, q_lens, window)
    for b in range(B):
        ql = q_lens[b]
        np.testing.assert_allclose(np.asarray(out[b, :ql]), ref[b, :ql],
                                   rtol=2e-5, atol=2e-5)
        # padding positions past q_len are exactly zero (the documented
        # contract) — in particular NOT NaN from an all-masked softmax row
        # inside a partially-valid q_block (m stays -inf there; the kernel
        # must zero the correction instead of computing exp(-inf + inf))
        np.testing.assert_array_equal(
            np.asarray(out[b, ql:]), np.zeros_like(np.asarray(out[b, ql:])))


def test_ragged_decode_rows_bit_identical_to_decode_kernel():
    """q_len=1 rows through the ragged kernel must be BIT-identical to
    paged_decode_attention — a decode row's token cannot depend on whether
    its round was mixed (prefill chunks present) or pure decode. This is the
    kernel-level half of the scheduler's stream bit-identity contract."""
    B, Hq, Hkv, D, page, Pmax = 4, 4, 2, 32, 16, 6
    N = B * Pmax + 2
    key = jax.random.PRNGKey(3)
    kq, kp = jax.random.split(key)
    q1 = jax.random.normal(kq, (B, Hq, D), jnp.float32)
    k_pool, v_pool, pt = _build_pool(kp, B, page, Pmax, Hkv, D, N)
    hist = jnp.asarray([0, 9, 33, 80], jnp.int32)

    dec = paged_decode_attention(q1, k_pool, v_pool,
                                 decode_work_list(pt, hist + 1, page),
                                 interpret=True)
    q = jnp.zeros((B, 8, Hq, D), jnp.float32).at[:, 0].set(q1)
    rag = ragged_paged_attention(q, k_pool, v_pool, pt, hist,
                                 jnp.ones((B,), jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(rag[:, 0]), np.asarray(dec))


def test_ragged_shared_prefix_pages():
    """Two rows sharing physical prefix pages (prefix-cache hit) while one
    decodes and the other chunk-prefills must each read the shared history
    correctly — sharing is rows in the page table, zero copies."""
    B, Hq, Hkv, D, page, Pmax = 2, 4, 2, 16, 8, 4
    N = 16
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, 8, Hq, D), jnp.float32)
    k_pool = jax.random.normal(kk, (1, N, page, Hkv * D), jnp.float32)
    v_pool = jax.random.normal(kv, (1, N, page, Hkv * D), jnp.float32)
    pt = jnp.asarray([[3, 7, 2, 0], [3, 7, 9, 0]], jnp.int32)
    hist = jnp.asarray([19, 16], jnp.int32)
    q_lens = jnp.asarray([1, 7], jnp.int32)

    out = ragged_paged_attention(q, k_pool, v_pool, pt, hist, q_lens,
                                 interpret=True)
    ref = _ref_rows(q, k_pool, v_pool, pt, [19, 16], [1, 7])
    for b in range(B):
        ql = int(q_lens[b])
        np.testing.assert_allclose(np.asarray(out[b, :ql]), ref[b, :ql],
                                   rtol=2e-5, atol=2e-5)


def test_ragged_idle_rows_are_zero_and_free():
    """q_len=0 rows produce all-zero output (empty softmax mass finalizes to
    0/eps) — the scheduler masks them host-side, but NaN/garbage here would
    poison the hidden-state pipeline of real rows if broadcast ops ever mix
    them, so pin the contract."""
    B, Hq, Hkv, D, page, Pmax = 2, 2, 2, 16, 8, 2
    N = 8
    key = jax.random.PRNGKey(2)
    kq, kp = jax.random.split(key)
    q = jax.random.normal(kq, (B, 8, Hq, D), jnp.float32)
    k_pool, v_pool, pt = _build_pool(kp, B, page, Pmax, Hkv, D, N)
    out = ragged_paged_attention(q, k_pool, v_pool, pt,
                                 jnp.asarray([5, 0], jnp.int32),
                                 jnp.asarray([1, 0], jnp.int32),
                                 interpret=True)
    assert np.all(np.asarray(out[1]) == 0.0)
    assert np.all(np.isfinite(np.asarray(out[0, 0])))


def test_ragged_rejects_misaligned_q_max():
    B, Hq, Hkv, D, page, Pmax = 1, 2, 2, 16, 8, 2
    k_pool, v_pool, pt = _build_pool(jax.random.PRNGKey(0), B, page, Pmax,
                                     Hkv, D, 4)
    q = jnp.zeros((B, 12, Hq, D), jnp.float32)  # 12 % 8 != 0
    with pytest.raises(ValueError, match="multiple of q_block"):
        ragged_paged_attention(q, k_pool, v_pool, pt,
                               jnp.zeros((B,), jnp.int32),
                               jnp.ones((B,), jnp.int32), interpret=True)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,Pmax,hist,q_lens,window", [
    # mixed GQA batch: decode row + page-crossing chunk + idle row
    (3, 4, 2, 16, 16, 6, [37, 12, 0], [1, 23, 0], None),
    # MHA (G=1) with a chunk starting exactly on a page boundary
    (2, 4, 4, 16, 8, 8, [16, 8], [16, 9], None),
    # sliding window + multiple q-block programs
    (3, 4, 2, 16, 16, 6, [40, 10, 25], [1, 14, 2], 24),
    (1, 2, 2, 16, 8, 8, [11, ], [33, ], None),
])
def test_two_d_dot_rewrite_bitwise(B, Hq, Hkv, D, page, Pmax, hist, q_lens,
                                   window):
    """The Mosaic-lowerable 2D-dot form of the ragged kernel (unrolled
    per-head slices/dots replacing the head-major [Qb,Hq,D]<->[Hq,Qb,D]
    shuffles and the batched GQA dot_generals) is BITWISE identical to the
    batched interpret form — the golden that lets the AOT path lower a
    different kernel body without any possibility of drift."""
    N = B * Pmax + 2
    key = jax.random.PRNGKey(7)
    kq, kp = jax.random.split(key)
    q_max = -(-max(q_lens) // 8) * 8
    q = jax.random.normal(kq, (B, q_max, Hq, D), jnp.float32)
    k_pool, v_pool, pt = _build_pool(kp, B, page, Pmax, Hkv, D, N)
    hist_a = jnp.asarray(hist, jnp.int32)
    qlen_a = jnp.asarray(q_lens, jnp.int32)

    batched = ragged_paged_attention(q, k_pool, v_pool, pt, hist_a, qlen_a,
                                     interpret=True, sliding_window=window,
                                     two_d_dots=False)
    two_d = ragged_paged_attention(q, k_pool, v_pool, pt, hist_a, qlen_a,
                                   interpret=True, sliding_window=window,
                                   two_d_dots=True)
    np.testing.assert_array_equal(np.asarray(two_d), np.asarray(batched))


def test_two_d_dot_rewrite_bitwise_decode_kernel():
    """Same golden for the decode (T=1) kernel's 2D form — the whole paged
    family must lower, so the whole family carries the rewrite."""
    B, Hq, Hkv, D, page, Pmax = 4, 4, 2, 32, 16, 6
    N = B * Pmax + 2
    key = jax.random.PRNGKey(11)
    kq, kp = jax.random.split(key)
    q = jax.random.normal(kq, (B, Hq, D), jnp.float32)
    k_pool, v_pool, pt = _build_pool(kp, B, page, Pmax, Hkv, D, N)
    lengths = jnp.asarray([1, 10, 34, 81], jnp.int32)

    work = decode_work_list(pt, lengths, page)
    batched = paged_decode_attention(q, k_pool, v_pool, work,
                                     interpret=True, two_d_dots=False)
    two_d = paged_decode_attention(q, k_pool, v_pool, work,
                                   interpret=True, two_d_dots=True)
    np.testing.assert_array_equal(np.asarray(two_d), np.asarray(batched))
