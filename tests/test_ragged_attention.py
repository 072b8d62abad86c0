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
    paged_decode_attention, paged_gather_dense,
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

    dec = paged_decode_attention(q1, k_pool, v_pool, pt, hist + 1,
                                 interpret=True, trip=1)
    q = jnp.zeros((B, 8, Hq, D), jnp.float32).at[:, 0].set(q1)
    rag = ragged_paged_attention(q, k_pool, v_pool, pt, hist,
                                 jnp.ones((B,), jnp.int32), interpret=True,
                                 trip=1)
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
    with pytest.raises(ValueError, match="not a multiple of 8"):
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

    forms = {}
    for trip in (1, None):
        forms[trip] = [np.asarray(ragged_paged_attention(
            q, k_pool, v_pool, pt, hist_a, qlen_a, interpret=True,
            sliding_window=window, two_d_dots=two_d, trip=trip))
            for two_d in (False, True)]
    # a page a trip: both forms' dots contract over the few numbers the
    # CPU sums in one order, and every bit agrees
    np.testing.assert_array_equal(*forms[1])
    # the shipped trip's value dot contracts over a key block of 128 and
    # more, which the CPU's batched and 2D dots sum in different orders
    np.testing.assert_allclose(*forms[None], rtol=0, atol=2e-6)


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

    batched = paged_decode_attention(q, k_pool, v_pool, pt, lengths,
                                     interpret=True, two_d_dots=False,
                                     trip=1)
    two_d = paged_decode_attention(q, k_pool, v_pool, pt, lengths,
                                   interpret=True, two_d_dots=True, trip=1)
    np.testing.assert_array_equal(np.asarray(two_d), np.asarray(batched))


# ---- the walk inside the program (PR 55): a (lane, q-block) a program

PAGE = 8


def _dense(q, k, v, hist, qlen, window=None, block=1, scale=None):
    """q [W, Hq, D], k / v [S, Hkv, D] (a lane's chain, gathered): each query
    ``t < qlen`` at position ``hist + t`` over the keys it sees, in float64;
    zeros past the span."""
    W, Hq, D = q.shape
    G = Hq // k.shape[1]
    pos = hist + np.arange(W)[:, None]
    keys = np.arange(k.shape[0])[None, :]
    seen = keys <= (pos | (block - 1))
    if window:
        seen &= keys > pos - window
    s = np.einsum("whd,shd->hws", q.astype(np.float64),
                  np.repeat(k, G, axis=1)) * (scale or D ** -0.5)
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    out = np.einsum("hws,shd->whd", p / p.sum(-1, keepdims=True),
                    np.repeat(v, G, axis=1))
    out[qlen:] = 0.0
    return out


def _lanes(rng, Hq, Hkv, D, width, hist, page=PAGE):
    """Three lanes' operands over a one-layer pool whose every page is in
    some lane's row of the table: (q, k_pool, v_pool, table)."""
    R = len(hist)
    pmax = -(-(max(hist) + width) // page) + 1
    n = R * pmax + 1
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (1, n, page, Hkv * D)), jnp.float32) for _ in range(2))
    table = (rng.permutation(n - 1)[: R * pmax] + 1).reshape(R, pmax)
    q = jnp.asarray(rng.standard_normal((R, width, Hq, D)), jnp.float32)
    return q, k_pool, v_pool, jnp.asarray(table, jnp.int32)


def _check_lanes(out, q, k_pool, v_pool, table, hist, qlens, **mask):
    D = q.shape[-1]
    k, v = (np.asarray(p[0])[np.asarray(table)].reshape(
        table.shape[0], -1, p.shape[-1] // D, D) for p in (k_pool, v_pool))
    for r, (h, n) in enumerate(zip(hist, qlens)):
        if n == 0:
            assert not np.asarray(out[r]).any()       # an idle lane: zeros
            continue
        np.testing.assert_allclose(
            np.asarray(out[r]),
            _dense(np.asarray(q[r]), k[r], v[r], h, n, **mask), atol=2e-5)


def _trip_edge(at, qlen, keys):
    """The history behind ``qlen`` queries whose last key is key ``at`` of a
    trip of ``keys`` keys (0: its first; -1: the last of the one before), a
    trip or more in."""
    return (-(-qlen // keys) + 1) * keys + at + 1 - qlen


@pytest.mark.parametrize("Hq,Hkv,width,hist,qlens,kw", [
    # history 0 and a history off a page; a span shorter than the width (33
    # of 64: its second q-block holds one query); an idle lane between
    (8, 2, 64, [0, 0, 5], [64, 0, 33], {}),
    # the last key one into a trip (of 16 pages: 128 keys) and on a trip's
    # last key
    (8, 2, 64, [_trip_edge(0, 64, 128), 0, _trip_edge(-1, 64, 128)],
     [64, 0, 64], {}),
    # one query head a kv head; a lane of 8 (a speculative span), a decode
    # row in it
    (2, 2, 8, [3, 0, 17], [1, 0, 8], {}),
    # 9 a kv head; 24 queries are one q-block, and the window binds inside it
    # (a trip is the 6 pages its windows span)
    (9, 1, 24, [30, 0, 2], [24, 0, 20], {"sliding_window": 12}),
    # 16 a kv head; the model's own softmax scale
    (16, 1, 64, [11, 0, 40], [50, 0, 64], {"scale": 0.3}),
    # a model that generates by blocks of 4: full inside a block
    (4, 2, 16, [8, 0, 24], [12, 0, 16], {"block": 4}),
    # a prompt's chunk: 8 q-blocks a lane, trips of 16 pages
    (4, 2, 512, [3, 0, 70], [500, 0, 512], {}),
], ids=["history-0-off-page-short-span", "trip-edges", "G1-width-8",
        "G9-width-24-window", "G16-scale", "block-mask", "width-512"])
def test_the_walk_against_the_dense_reference(Hq, Hkv, width, hist, qlens,
                                              kw):
    """A (lane, q-block) a program, the pages of its span a trip at a time
    (the shipped q-block and trip, from shapes): every query against plain
    float64 attention over its lane's gathered chain; the idle lane between
    two busy ones and the padding past a span are zeros."""
    rng = np.random.default_rng(width + Hq)
    q, k_pool, v_pool, table = _lanes(rng, Hq, Hkv, 16, width, hist)
    out = ragged_paged_attention(
        q, k_pool, v_pool, table, jnp.asarray(hist, jnp.int32),
        jnp.asarray(qlens, jnp.int32), interpret=True, **kw)
    assert out.shape == q.shape
    mask = {{"sliding_window": "window"}.get(k, k): v for k, v in kw.items()}
    _check_lanes(out, q, k_pool, v_pool, table, hist, qlens, **mask)


@pytest.mark.parametrize("window,trip,two_d", [(None, None, False),
                                               (16, None, True),
                                               (40, 2, False)])
def test_the_walk_with_the_rings_and_the_pools_poisoned(window, trip, two_d):
    """TPU interpret mode: memory no one wrote reads NaN (so do the rings
    and the accumulators before a call), a DMA lands when it is waited for,
    and an access that races one is reported. Every page of both pools
    outside the lanes' spans is NaN too (the pages past a lane's last key,
    the pages left of its first query's window, a page no table names):
    nothing outside a span is copied, nothing stale in a key block is
    attended over."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    width, hist, qlens = 64, [37, 0, 150, 9], [64, 0, 40, 23]
    q, k_pool, v_pool, table = _lanes(np.random.default_rng(7), 4, 2, 16,
                                      width, hist)
    live = k_pool, v_pool
    dead = np.ones(k_pool.shape[1], bool)
    for r, (h, n) in enumerate(zip(hist, qlens)):
        first = max(h + 1 - window, 0) // PAGE if window and n else 0
        last = (h + n - 1) // PAGE if n else -1
        dead[np.asarray(table[r, first:last + 1])] = False
    k_pool, v_pool = (p.at[:, dead].set(np.nan) for p in live)
    out = ragged_paged_attention(
        q, k_pool, v_pool, table, jnp.asarray(hist, jnp.int32),
        jnp.asarray(qlens, jnp.int32),
        interpret=pltpu.InterpretParams(detect_races=True),
        sliding_window=window, trip=trip, two_d_dots=two_d)
    assert not interpret_pallas_call.races.races_found
    assert np.isfinite(np.asarray(out)).all()
    _check_lanes(out, q, *live, table, hist, qlens, window=window)


@pytest.mark.parametrize("window,trip,heads", [(None, None, (4, 2)),
                                               (None, 2, (4, 2)),
                                               (16, None, (9, 1))])
def test_the_copies_a_call_starts_are_what_the_host_counts(
        counted_copies, window, trip, heads):
    """The scheduler counts ``llm_ragged_pages_walked_total`` and
    ``llm_ragged_trips_total`` at a mixed step's dispatch by the kernel's own
    ``ragged_span`` on NumPy (``ragged_walk``): the kernel starts one copy
    in each pool for each of those pages, waits for each, and attends over
    that many key blocks; an idle lane and the q-blocks past a span's end
    cost neither."""
    from cyberfabric_core_tpu.ops.paged_attention import (
        ragged_q_block, ragged_trip_pages, ragged_walk)

    width, hist, qlens, pmax = 64, [5, 0, 150, 64], [13, 0, 64, 33], 28
    pages, trips = ragged_walk(hist, qlens, width, PAGE, pmax, window,
                               heads[0], trip=trip)
    # by hand: the pages that hold a key some query of a q-block sees
    q_block = ragged_q_block(width, heads[0])
    t = trip or ragged_trip_pages(PAGE, window, q_block)
    assert (q_block, t) == (64, trip or (16 if window is None else 11))
    want = [0, 0]
    for h, n in zip(hist, qlens):
        for q0 in range(0, n, q_block):
            seen = {k // PAGE for pos in range(h + q0, h + min(n, q0 + q_block))
                    for k in range(max(pos - window + 1, 0) if window else 0,
                                   pos + 1)}
            want[0] += len(seen)
            want[1] += -(-len(seen) // t)
    assert [pages, trips] == want and pages == {None: 3 + 27 + 13,
                                                16: 3 + 11 + 7}[window]
    rng = np.random.default_rng(0)
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (1, 4 * pmax + 1, PAGE, heads[1] * 16)), jnp.float32)
        for _ in range(2))
    table = jnp.asarray(np.arange(1, 4 * pmax + 1).reshape(4, pmax),
                        jnp.int32)
    seen = counted_copies(
        f"counted_kv_ragged_{window}_{trip}",
        jnp.ones((4, width, heads[0], 16)), k_pool, v_pool, table,
        jnp.asarray(hist, jnp.int32), jnp.asarray(qlens, jnp.int32), 0,
        sliding_window=window, trip=trip, kernel=ragged_paged_attention)
    assert (seen["start"], seen["wait"], seen["trips"]) == (
        2 * pages, 2 * pages, trips)


def test_the_q_block_and_the_trip_come_from_shapes():
    """64 queries a program, a lane's width where that is less, halved for as
    long as the accumulators of that many rows would pass their share of
    VMEM; a trip is the pages of 1 024 keys (no more than 16), and no more
    than a q-block's windows span."""
    from cyberfabric_core_tpu.ops.paged_attention import (kv_block_sizes,
                                                          ragged_q_block,
                                                          ragged_trip_pages)

    # a trip is attended over as a block of 4 pages or as the whole trip
    assert [kv_block_sizes(t) for t in (1, 2, 4, 10, 16)] == [
        (1,), (2,), (4,), (4, 10), (4, 16)]
    assert [ragged_q_block(w, 72) for w in (8, 16, 24, 64, 512)] == \
        [8, 16, 24, 64, 64]
    assert ragged_q_block(512, 85) == 64 and ragged_q_block(512, 86) == 32
    assert ragged_q_block(512, 1024) == 8 == ragged_q_block(8, 1024)
    # laguna's window layers: the windows of 64 queries span 10 pages, ONE
    # trip; its full layers and every other model: 16
    assert ragged_trip_pages(64, 512, 64) == 10
    assert ragged_trip_pages(64, None, 64) == 16
    assert ragged_trip_pages(64, 4096, 64) == 16
    assert ragged_trip_pages(64, 64, 32) == 3
    assert ragged_trip_pages(8, None, 64) == 16
