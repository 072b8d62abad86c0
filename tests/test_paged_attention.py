"""Paged decode attention kernel vs dense reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops.attention import attention_with_cache
from cyberfabric_core_tpu.ops.paged_attention import (
    decode_work_list, page_span, paged_decode_attention, paged_gather_dense)
from test_paged_kernel_goldens import HEADS as GROUP_HEADS


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


@pytest.mark.parametrize("group", [1, 2, 4, 8])
@pytest.mark.parametrize("window", [None, 24, 16, 1])
def test_work_list_names_the_pages_that_hold_tokens(window, group):
    """Rows in order, a row's groups ascending from the window's first page
    to the page of its own token, every row at least one item, and nothing
    else in the first ``n_items``: the groups' pages, flattened and with the
    spare operands of a row's last group dropped, are the pages in use. The
    host's counts by ``page_span`` on NumPy arrays (the /metrics counters)
    are the device's."""
    page, Pmax = 16, 6
    lengths = np.asarray([0, 1, 16, 17, 96, 50, 200, 31], np.int32)
    B = len(lengths)
    pt = np.arange(1, B * Pmax + 1, dtype=np.int32).reshape(B, Pmax)
    # a constant table under a caller's jit, as chip_smoke.py has it
    work = jax.jit(
        lambda n: decode_work_list(pt, n, page, window, group))(lengths)

    want, programs = [], 0
    for b, n in enumerate(lengths):
        last = min(max((n - 1) // page, 0), Pmax - 1)   # a length past the
        lo = 0                                           # table walks it all
        if window is not None:
            lo = next((j for j in range(last + 1)
                       if (j + 1) * page > n - window), last)
        want += [(b, j, int(pt[b, j])) for j in range(lo, last + 1)]
        programs += -(-(last + 1 - lo) // group)
    n = int(work.n_items)
    assert n == programs and work.group == group
    rows, firsts = (np.asarray(a)[:n] for a in (work.row, work.page))
    phys = np.asarray(work.phys).reshape(-1, group)[:n]
    got = [(int(b), int(j0) + g, int(phys[i, g]))
           for i, (b, j0) in enumerate(zip(rows, firsts))
           for g in range(group) if j0 + g <= int(np.asarray(work.last)[b])]
    assert got == want
    # as many items as a full table needs
    assert work.row.shape == (B * -(-Pmax // group),)
    # spare operands and the items past n_items still name pages of the table
    assert set(np.asarray(work.phys).tolist()) <= set(pt.ravel().tolist())
    # a spare operand holds the page the same operand held in the item before
    for i in range(1, n):
        for g in range(group):
            if firsts[i] + g > int(np.asarray(work.last)[rows[i]]):
                assert phys[i, g] == phys[i - 1, g]

    first, last = page_span(lengths, page, Pmax, window)
    assert isinstance(last, np.ndarray)
    assert int((last - first + 1).sum()) == len(want)
    assert int(((last - first) // group + 1).sum()) == n


@pytest.mark.parametrize("body", ["batched", "two_d_dots"])
@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("group", [2, 4, 8])
@pytest.mark.parametrize("heads", list(GROUP_HEADS))  # the goldens' five
def test_a_group_of_pages_is_the_pages_one_at_a_time(heads, group, window,
                                                     body):
    """A program that takes ``group`` pages gives what one page a program
    gives (the online softmax rescales at other places, so to the last bits
    of the pages' type) and what attention over the gathered dense cache
    gives: rows whose span is shorter than a group, exactly one, one page
    longer, a full table, an empty row, and two rows that share their first
    pages."""
    Hq, Hkv, D, dtype = GROUP_HEADS[heads]
    page, Pmax = 16, 10
    full = group * page
    lengths = [full - page - 3, full, full + 1, Pmax * page, 0, full + 9, 5]
    B, N = len(lengths), len(lengths) * Pmax + 2
    rng = np.random.default_rng(group)
    norm = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape, np.float32), dtype)
    q, k_pool, v_pool = (norm(B, Hq, D), norm(2, N, page, Hkv * D),
                         norm(2, N, page, Hkv * D))
    pt = (rng.permutation(N - 1)[: B * Pmax] + 1).reshape(B, Pmax)
    pt[5, :2] = pt[2, :2]                 # a shared prefix of two pages
    pt, lens = jnp.asarray(pt, jnp.int32), jnp.asarray(lengths, jnp.int32)

    def attend(g):
        return np.asarray(paged_decode_attention(
            q, k_pool, v_pool, decode_work_list(pt, lens, page, window, g), 1,
            interpret=True, sliding_window=window,
            two_d_dots=body == "two_d_dots"), np.float32)

    out, one = attend(group), attend(1)
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, D, layer=1)
    ref = attention_with_cache(
        q[:, None].astype(jnp.float32), k_dense.astype(jnp.float32),
        v_dense.astype(jnp.float32), (lens - 1)[:, None], lens,
        sliding_window=window)[:, 0]
    ref = np.asarray(jnp.where((lens > 0)[:, None, None], ref, 0.0))
    # bfloat16 keeps 8 bits: the output's own rounding, and one more where
    # the accumulator was rescaled elsewhere
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(out, one, rtol=tol, atol=tol)
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    assert not out[4].any()               # the empty row finalises to zeros


@pytest.mark.parametrize("group", [1, 4])
def test_tp_shard_mapped_call_takes_the_grouped_work_list(group):
    """Under tensor parallelism the kernel runs shard-mapped over the kv
    heads with the work list replicated: every field of ``DecodeWork`` has
    its spec, and each device's head slice gives its rows of the one-device
    result, bit for bit."""
    from jax.sharding import Mesh

    from cyberfabric_core_tpu.models.configs import get_config
    from cyberfabric_core_tpu.models.llama import _decode_attend

    cfg = get_config("tiny-llama")
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    tp = 2
    assert Hkv % tp == 0
    B, page, Pmax = 3, 16, 6
    N = B * Pmax + 2
    rng = np.random.default_rng(group)
    q = jnp.asarray(rng.standard_normal((B, Hq, D), np.float32))
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (2, N, page, Hkv * D), np.float32)) for _ in range(2))
    pt = jnp.asarray((rng.permutation(N - 1)[: B * Pmax] + 1).reshape(B, Pmax),
                     jnp.int32)
    work = decode_work_list(pt, jnp.asarray([70, 0, 96], jnp.int32), page,
                            cfg.sliding_window, group)
    mesh = Mesh(np.asarray(jax.devices()[:tp]), ("tp",))
    one = _decode_attend(cfg, True, None)(q, k_pool, v_pool, work, 1)
    sharded = jax.jit(_decode_attend(cfg, True, mesh))(
        q, k_pool, v_pool, work, jnp.asarray(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(one))


@pytest.mark.parametrize("name,page,kv_lanes,itemsize,q_rows,n_pages,want", [
    ("mistral-7b", 64, 8 * 128, 2, 32, 32, 4),     # 1 MB of K and V a group
    ("qwen2-7b", 64, 4 * 128, 2, 28, 64, 8),
    ("falcon-h1-34b", 64, 4 * 128, 2, 20, 32, 8),
    ("sdar folded", 64, 4 * 128, 2, 128, 32, 8),
    ("float32 pages", 64, 8 * 128, 4, 32, 32, 2),  # twice the bytes a page
    ("mha 32 x 128", 64, 32 * 128, 2, 32, 32, 1),  # a page is 1 MB alone
    ("a short table", 16, 2 * 16, 4, 4, 6, 4),     # no more than a row has
    ("one page a row", 16, 2 * 16, 4, 4, 1, 1),
    ("a tiny page", 16, 2 * 16, 4, 4, 128, 8),     # at most 8
    ("many query rows", 64, 128, 2, 4096, 64, 2),  # the score tile's VMEM
])
def test_the_group_follows_from_the_shapes(name, page, kv_lanes, itemsize,
                                           q_rows, n_pages, want):
    from cyberfabric_core_tpu.ops.paged_attention import decode_page_group

    assert decode_page_group(page, kv_lanes, itemsize, q_rows, n_pages) == want
