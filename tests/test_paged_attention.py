"""Paged decode attention kernel vs dense reference (interpret mode on CPU):
a program a row that walks its pages itself (``ops/page_walk.py``), a trip of
pages one key block, a kv head over its own query rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops.attention import attention_with_cache
from cyberfabric_core_tpu.ops.paged_attention import (
    kv_block_sizes, decode_trip_pages, page_span, paged_decode_attention,
    paged_gather_dense)
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
    # the walk is the pages in use: an empty row (no trip, zeros out), one
    # token, exactly a page, a full table
    (4, 4, 2, 32, 16, 4, [0, 1, 16, 64], None),
    (3, 28, 4, 128, 16, 4, [64, 0, 17], None),
    # a window that binds: a row's first pages are not walked at all
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

    out = paged_decode_attention(q, k_pool, v_pool, pt, lens,
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

    out = paged_decode_attention(q, k_pool, v_pool, pt, lens,
                                 interpret=True)
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, D)
    ref = attention_with_cache(q[:, None], k_dense, v_dense,
                               (lens - 1)[:, None], lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _dense(q, k_pool, v_pool, pt, lens, D, window=None, layer=0, scale=None):
    """Attention over the gathered dense cache in float32 at q_pos = len - 1;
    zeros for an empty row."""
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, pt, D, layer=layer)
    q = q.astype(jnp.float32)
    if scale is not None:           # the reference scales by D^-1/2
        q = q * (scale * D ** 0.5)
    ref = attention_with_cache(
        q[:, None], k_dense.astype(jnp.float32), v_dense.astype(jnp.float32),
        (lens - 1)[:, None], lens, sliding_window=window)[:, 0]
    return np.asarray(jnp.where((lens > 0)[:, None, None], ref, 0.0))


@pytest.mark.parametrize("trip", [1, 2, 4, 8])
@pytest.mark.parametrize("window", [None, 24, 16, 1])
def test_the_span_names_the_pages_that_hold_tokens(counted_copies, window,
                                                   trip):
    """Rows in order, a row's trips ascending from the window's first page
    to the page of its own token, no trip for an empty row, and nothing
    else: the kernel starts one copy in each pool for each page in use
    (worked out by hand here) and waits for each, attends over that many
    key blocks, and, with every OTHER page of both pools NaN, gives what the
    dense reference gives. The host's counts by ``page_span`` on NumPy
    arrays (the /metrics counters) are the device's, but for the one page
    and one trip an empty row is counted as."""
    page, Pmax, Hq, Hkv, D = 16, 6, 4, 2, 16
    lengths = np.asarray([0, 1, 16, 17, 96, 50, 200, 31], np.int32)
    B = len(lengths)
    pt = np.arange(1, B * Pmax + 1, dtype=np.int32).reshape(B, Pmax)
    want, trips = [], 0
    for b, n in enumerate(lengths):
        if not n:
            continue
        last = min(max((n - 1) // page, 0), Pmax - 1)   # a length past the
        lo = 0                                           # table walks it all
        if window is not None:
            lo = next((j for j in range(last + 1)
                       if (j + 1) * page > n - window), last)
        want += [int(pt[b, j]) for j in range(lo, last + 1)]
        trips += -(-(last + 1 - lo) // trip)
    rng = np.random.default_rng(trip)
    live = [jnp.asarray(rng.standard_normal((1, B * Pmax + 1, page, Hkv * D),
                                            np.float32)) for _ in range(2)]
    dead = np.ones(B * Pmax + 1, bool)
    dead[want] = False
    k_pool, v_pool = (p.at[:, dead].set(np.nan) for p in live)
    q = jnp.asarray(rng.standard_normal((B, Hq, D), np.float32))
    out = {}
    seen = counted_copies(
        f"counted_kv_decode_{window}_{trip}", q, k_pool, v_pool,
        jnp.asarray(pt), jnp.asarray(lengths), 0, sliding_window=window,
        trip=trip,
        kernel=lambda *a, **kw: out.setdefault(
            "o", paged_decode_attention(*a, **kw)))
    assert (seen["start"], seen["wait"], seen["trips"]) == (
        2 * len(want), 2 * len(want), trips)
    # (the row whose length lies past the table reads the table and no
    # further, from its own window's start: not what the reference masks)
    got, inside = np.asarray(out["o"]), lengths <= Pmax * page
    ref = _dense(q, *live, jnp.asarray(pt), jnp.asarray(lengths), D, window)
    np.testing.assert_allclose(got[inside], ref[inside], rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all() and not got[0].any()

    first, last = page_span(lengths, page, Pmax, window)
    assert isinstance(last, np.ndarray)
    assert int((last - first + 1).sum()) == len(want) + 1
    assert int(((last - first) // trip + 1).sum()) == trips + 1


@pytest.mark.parametrize("body", ["batched", "two_d_dots"])
@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("trip", [2, 4, 8])
@pytest.mark.parametrize("heads", list(GROUP_HEADS))  # the goldens' five
def test_a_trip_of_pages_is_the_pages_one_at_a_time(heads, trip, window,
                                                    body):
    """A trip of ``trip`` pages gives what one page a trip gives (the online
    softmax rescales at other places, so to the last bits of the pages'
    type) and what attention over the gathered dense cache gives: rows
    whose span is shorter than a trip, exactly one, one page longer, a full
    table, an empty row, and two rows that share their first pages."""
    Hq, Hkv, D, dtype = GROUP_HEADS[heads]
    page, Pmax = 16, 10
    full = trip * page
    lengths = [full - page - 3, full, full + 1, Pmax * page, 0, full + 9, 5]
    B, N = len(lengths), len(lengths) * Pmax + 2
    rng = np.random.default_rng(trip)
    norm = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape, np.float32), dtype)
    q, k_pool, v_pool = (norm(B, Hq, D), norm(2, N, page, Hkv * D),
                         norm(2, N, page, Hkv * D))
    pt = (rng.permutation(N - 1)[: B * Pmax] + 1).reshape(B, Pmax)
    pt[5, :2] = pt[2, :2]                 # a shared prefix of two pages
    pt, lens = jnp.asarray(pt, jnp.int32), jnp.asarray(lengths, jnp.int32)

    def attend(t):
        return np.asarray(paged_decode_attention(
            q, k_pool, v_pool, pt, lens, 1, interpret=True,
            sliding_window=window, two_d_dots=body == "two_d_dots", trip=t),
            np.float32)

    out, one = attend(trip), attend(1)
    ref = _dense(q, k_pool, v_pool, pt, lens, D, window, layer=1)
    # bfloat16 keeps 8 bits: the output's own rounding, and one more where
    # the accumulator was rescaled elsewhere
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(out, one, rtol=tol, atol=tol)
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    assert not out[4].any()               # the empty row finalises to zeros


#: (Hq, Hkv, D): query rows a kv head's slab holds and is padded from
SLABS = {
    "G1": (4, 4, 32), "G5": (10, 2, 32), "G6": (12, 2, 32), "G9": (9, 1, 32),
    "G16": (32, 2, 32), "G32-folded": (64, 2, 32), "head-of-96": (8, 4, 96),
}
#: what a row can be, at pages of 16 and trips of 3: (lengths, window, scale)
ROWS = {
    "one-token": ([1, 1, 1], None, None),
    "empty-between-busy": ([70, 0, 41], None, None),
    "a-trips-last-key": ([48, 96, 144], None, None),
    "one-past-a-trip": ([49, 97, 145], None, None),
    "window-inside-a-page": ([70, 100, 27], 21, None),
    "scale": ([70, 100, 27], None, 0.37),
}


@pytest.mark.parametrize("slab,rows", [
    *[(slab, "empty-between-busy") for slab in SLABS],
    *[("G6", rows) for rows in ROWS if rows != "empty-between-busy"],
    ("G9", "window-inside-a-page"), ("head-of-96", "one-past-a-trip"),
])
def test_the_padded_slab_against_the_dense_reference(slab, rows):
    """A kv head's ``G`` query rows ride as one slab padded to whole sublane
    tiles and the padding rows are dropped on the way back: every ``G`` the
    cells serve (ouro's 1, falcon's 5, laguna's 6 and 9, nemotron's 16,
    sdar's folded 32) and a head that is no whole lane tile, over rows of
    one token, an empty row between two busy ones, a length on a trip's
    last key and one past it, a window that starts inside a page, and a
    softmax scale of the model's own; in the Mosaic body's order of dots."""
    Hq, Hkv, D = SLABS[slab]
    lengths, window, scale = ROWS[rows]
    page, Pmax, B = 16, 10, 3
    rng = np.random.default_rng(Hq + len(rows))
    N = B * Pmax + 2
    q = jnp.asarray(rng.standard_normal((B, Hq, D), np.float32))
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (2, N, page, Hkv * D), np.float32)) for _ in range(2))
    pt = jnp.asarray((rng.permutation(N - 1)[: B * Pmax] + 1).reshape(B, Pmax),
                     jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    out = paged_decode_attention(
        q, k_pool, v_pool, pt, lens, 1, interpret=True, sliding_window=window,
        scale=scale, two_d_dots=True, trip=3)
    assert out.shape == (B, Hq, D)
    np.testing.assert_allclose(
        np.asarray(out), _dense(q, k_pool, v_pool, pt, lens, D, window,
                                layer=1, scale=scale), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths,window,trip,two_d", [
    ([48, 70, 33], None, 3, True),          # spans of exactly T and T + 1
    ([0, 0, 45, 90], None, 2, False),       # idle rows first
    ([45, 0, 0, 90], 40, 2, True),          # ... between, behind a window
    ([45, 90, 0, 0], None, None, True),     # ... last, the shipped trip
    ([0, 0, 0], None, 2, True),             # nothing to walk: no copy
    ([1], None, None, False),               # B = 1, a span of one page
    ([160, 3, 160, 0, 160], 100, 1, True),  # more trips in flight than a row
], ids=["T-and-T+1", "idle-first", "idle-between-window", "idle-last",
        "all-idle", "one-page", "trip-1-window"])
def test_decode_kernel_with_the_rings_and_the_pools_poisoned(lengths, window,
                                                             trip, two_d):
    """TPU interpret mode: memory no one wrote reads NaN (so do the rings
    and the accumulators before a call), a DMA lands when it is waited for,
    and an access that races one is reported. Every page of both pools
    outside the rows' spans is NaN too (past a row's last key, left of its
    window, a page no table names): nothing outside a span is copied, and
    nothing stale in a key block is attended over."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    page, Pmax, Hq, Hkv, D = 16, 10, 6, 2, 16
    B = len(lengths)
    N = B * Pmax + 1
    rng = np.random.default_rng(sum(lengths))
    live = [jnp.asarray(rng.standard_normal((2, N, page, Hkv * D),
                                            np.float32)) for _ in range(2)]
    table = np.arange(1, N, dtype=np.int32).reshape(B, Pmax)
    dead = np.ones(N, bool)
    for b, n in enumerate(lengths):
        first = max(n - window, 0) // page if window else 0
        dead[table[b, first:-(-n // page)]] = False
    k_pool, v_pool = (p.at[:, dead].set(np.nan) for p in live)
    q = jnp.asarray(rng.standard_normal((B, Hq, D), np.float32))
    lens = jnp.asarray(lengths, jnp.int32)
    out = np.asarray(paged_decode_attention(
        q, k_pool, v_pool, jnp.asarray(table), lens, 1,
        interpret=pltpu.InterpretParams(detect_races=True),
        sliding_window=window, trip=trip, two_d_dots=two_d))
    assert not interpret_pallas_call.races.races_found
    assert np.isfinite(out).all()
    np.testing.assert_allclose(
        out, _dense(q, *live, jnp.asarray(table), lens, D, window, layer=1),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("trip", [1, 4, 8])
def test_the_copies_a_call_starts_are_what_the_walked_counter_counts(
        counted_copies, trip):
    """The scheduler counts ``llm_attn_pages_walked_total`` from the host's
    length mirror by the kernel's own ``page_span``: sum over rows of (last -
    first + 1), of ``B x Pmax`` offered, a row that holds nothing counted as
    the one page its program costs. The kernel starts one copy in EACH pool
    for each of those pages (none for the row that holds nothing), and waits
    for each; a row's trips are the groups ``llm_attn_page_groups_total``
    counts, and a trip is attended over as the smallest block that holds
    it (the latent kernel's twin: tests/test_mla_attention.py)."""
    page, lengths = 8, np.array([13, 0, 48, 9, 1, 17])
    table = jnp.asarray(np.arange(1, 37).reshape(6, 6), jnp.int32)
    first, last = page_span(lengths, page, 6, None)
    walked = int((last - first + 1).sum())
    assert walked == 2 + 1 + 6 + 2 + 1 + 3
    rng = np.random.default_rng(0)
    k_pool, v_pool = (jnp.asarray(rng.standard_normal((1, 40, page, 2 * 16)),
                                  jnp.float32) for _ in range(2))
    seen = counted_copies(
        f"counted_kv_{trip}", jnp.ones((6, 4, 16)), k_pool, v_pool, table,
        jnp.asarray(lengths, jnp.int32), 0, trip=trip,
        kernel=paged_decode_attention)
    assert (seen["start"], seen["wait"]) == (2 * (walked - 1),
                                            2 * (walked - 1))
    assert seen["trips"] == int(((last - first) // trip + 1).sum()) - 1
    assert kv_block_sizes(trip) == {1: (1,), 4: (4,), 8: (4, 8)}[trip]
    blocks = {k: v for k, v in seen.items() if k.startswith("trips_of_")}
    assert blocks == {1: {"trips_of_1": 14},
                      4: {"trips_of_4": 6},
                      8: {"trips_of_4": 4, "trips_of_8": 1}}[trip]


@pytest.mark.parametrize("lengths", [[70, 0, 96], [1, 33, 17]],
                         ids=["an-empty-row", "short-rows"])
def test_tp_shard_mapped_call_takes_the_table_and_the_lengths(lengths):
    """Under tensor parallelism the kernel runs shard-mapped over the kv
    heads with the table and the lengths replicated, and each device's head
    slice gives its rows of the one-device result, bit for bit."""
    from jax.sharding import Mesh

    from cyberfabric_core_tpu.models.configs import get_config
    from cyberfabric_core_tpu.models.llama import (_decode_attend,
                                                   decode_work)

    cfg = get_config("tiny-llama")
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    tp = 2
    assert Hkv % tp == 0
    B, page, Pmax = 3, 16, 6
    N = B * Pmax + 2
    rng = np.random.default_rng(sum(lengths))
    q = jnp.asarray(rng.standard_normal((B, Hq, D), np.float32))
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (2, N, page, Hkv * D), np.float32)) for _ in range(2))
    pt = jnp.asarray((rng.permutation(N - 1)[: B * Pmax] + 1).reshape(B, Pmax),
                     jnp.int32)
    work = decode_work(pt, jnp.asarray(lengths, jnp.int32))
    mesh = Mesh(np.asarray(jax.devices()[:tp]), ("tp",))
    one = _decode_attend(cfg, True, None)(q, k_pool, v_pool, work, 1)
    sharded = jax.jit(_decode_attend(cfg, True, mesh))(
        q, k_pool, v_pool, work, jnp.asarray(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(one))


@pytest.mark.parametrize("name,page,kv_lanes,itemsize,n_pages,window,want", [
    ("mistral-7b", 64, 8 * 128, 2, 32, 4096, 16),  # 2 MB of K a key block
    ("qwen2-7b", 64, 4 * 128, 2, 64, None, 16),
    ("falcon-h1-34b", 64, 4 * 128, 2, 32, None, 16),
    ("ouro-2.6b", 64, 16 * 128, 2, 13, None, 8),   # twice the bytes a page
    ("laguna window", 64, 8 * 128, 2, 128, 512, 9),    # what a window spans
    ("laguna full", 64, 8 * 128, 2, 128, None, 16),
    ("nemotron-h", 64, 2 * 128, 2, 64, None, 16),  # no more than TRIP_PAGES
    ("float32 pages", 64, 8 * 128, 4, 32, None, 8),
    ("mha 32 x 128", 64, 32 * 128, 2, 32, None, 4),
    ("a shard of tp 2", 64, 4 * 128, 2, 32, None, 16),  # its own lanes
    ("a short table", 16, 2 * 16, 4, 6, None, 6),   # no more than a row has
    ("one page a row", 16, 2 * 16, 4, 1, None, 1),
    ("a window of a page", 16, 2 * 16, 4, 128, 16, 2),
])
def test_the_trip_follows_from_the_shapes(name, page, kv_lanes, itemsize,
                                          n_pages, window, want):
    assert decode_trip_pages(page, kv_lanes, itemsize, n_pages,
                             window) == want
    # a trip is attended over as a block of 4 pages or as the whole trip
    assert kv_block_sizes(want) == tuple(sorted({min(4, want), want}))
