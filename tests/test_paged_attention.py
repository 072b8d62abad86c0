"""Paged decode attention kernel vs dense reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops.attention import attention_with_cache
from cyberfabric_core_tpu.ops.paged_attention import (
    decode_work_list, page_span, paged_decode_attention, paged_gather_dense)


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
    # the grid is the pages in use: an empty row (one item that computes
    # nothing, zeros out), one token, exactly a page, a full table
    (4, 4, 2, 32, 16, 4, [0, 1, 16, 64], None),
    (3, 28, 4, 128, 16, 4, [64, 0, 17], None),
    # a window that binds: a row's first pages are not in the grid at all
    (4, 4, 2, 32, 16, 4, [64, 0, 49, 16], 24),
    (3, 32, 8, 128, 16, 4, [64, 41, 1], 24),
    (2, 4, 2, 32, 16, 4, [64, 33], 16),        # the window is one page
])
def test_paged_matches_dense(B, Hq, Hkv, D, page, Pmax, lengths, window):
    N = B * Pmax + 2
    key = jax.random.PRNGKey(0)
    kq, kp = jax.random.split(key)
    q = jax.random.normal(kq, (B, Hq, D), jnp.float32)
    k_pool, v_pool, pt = _build_pool(kp, B, lengths, page, Pmax, Hkv, D, N)
    lens = jnp.asarray(lengths, jnp.int32)

    work = decode_work_list(pt, lens, page, window)
    out = paged_decode_attention(q, k_pool, v_pool, work,
                                 interpret=True, sliding_window=window)

    # dense reference: gather pages, then standard attention at q_pos = len-1
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, D)
    q_pos = (lens - 1)[:, None]
    ref = attention_with_cache(q[:, None], k_dense, v_dense, q_pos, lens,
                               sliding_window=window)[:, 0]
    ref = jnp.where((lens > 0)[:, None, None], ref, 0.0)   # an empty row
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

    out = paged_decode_attention(q, k_pool, v_pool,
                                 decode_work_list(pt, lens, page),
                                 interpret=True)
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, D)
    ref = attention_with_cache(q[:, None], k_dense, v_dense,
                               (lens - 1)[:, None], lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 24, 16, 1])
def test_work_list_names_the_pages_that_hold_tokens(window):
    """Rows in order, a row's pages ascending from the window's first to the
    page of its own token, every row at least one item, and nothing else in
    the first ``n_items``; the host's count by ``page_span`` on NumPy arrays
    (the /metrics counter) is the device's."""
    page, Pmax = 16, 6
    lengths = np.asarray([0, 1, 16, 17, 96, 50, 200, 31], np.int32)
    B = len(lengths)
    pt = np.arange(1, B * Pmax + 1, dtype=np.int32).reshape(B, Pmax)
    # a constant table under a caller's jit, as chip_smoke.py has it
    work = jax.jit(lambda n: decode_work_list(pt, n, page, window))(lengths)

    want = []
    for b, n in enumerate(lengths):
        last = min(max((n - 1) // page, 0), Pmax - 1)   # a length past the
        lo = 0                                           # table walks it all
        if window is not None:
            lo = next((j for j in range(last + 1)
                       if (j + 1) * page > n - window), last)
        want += [(b, j, int(pt[b, j])) for j in range(lo, last + 1)]
    n = int(work.n_items)
    assert n == len(want)
    got = list(zip(*(np.asarray(a)[:n].tolist()
                     for a in (work.row, work.page, work.phys))))
    assert got == want
    assert work.row.shape == (B * Pmax,)      # as many as a full table needs
    # items past n_items are never run, and still name pages of the table
    assert set(np.asarray(work.phys)[n:].tolist()) <= set(
        np.asarray(pt).ravel().tolist())

    first, last = page_span(lengths, page, Pmax, window)
    assert isinstance(last, np.ndarray)
    assert int((last - first + 1).sum()) == n
