"""The latent attention kernels (ops/mla_attention.py) in interpret mode
against plain ``jnp``: ragged lengths, idle rows, a page read once as key
(all its lanes) and as value (its first ``rank``); and both kernels' walk:
the copies a call starts are the pages the host's counters count, its key
blocks may hold anything before a call, every start has its wait."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from cyberfabric_core_tpu.ops import mla_attention as mla
from cyberfabric_core_tpu.ops.mla_attention import (TRIP_PAGES,
                                                    mla_decode_attention,
                                                    mla_ragged_attention,
                                                    ragged_q_block,
                                                    ragged_trip_pages,
                                                    ragged_walk,
                                                    trip_pages)
from cyberfabric_core_tpu.ops.page_walk import (RING_BLOCKS, _block_sizes,
                                                 page_span)

RANK, ROPE, LANES, HQ, PAGE = 32, 16, 128, 4, 8
SCALE = 0.21


def _pool(rng, layers=2, pages=40):
    """Latent rows in whole lane tiles: RANK + ROPE numbers, then zeros."""
    pool = np.zeros((layers, pages, PAGE, LANES), np.float32)
    pool[..., : RANK + ROPE] = rng.standard_normal(
        (layers, pages, PAGE, RANK + ROPE))
    return jnp.asarray(pool)


def _dense(q, rows, n):
    """q [Hq, LANES], rows [S, LANES]: softmax(q.k sigma) over the first n."""
    s = (q @ rows[:n].T) * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:n, :RANK]


def _table(rng, B, pmax, pages=40):
    return jnp.asarray(rng.permutation(np.arange(1, pages))[: B * pmax]
                       .reshape(B, pmax), jnp.int32)


def _check(out, q, pool, layer, table, lengths):
    for b, n in enumerate(lengths):
        if n == 0:
            assert not out[b].any()          # an idle row finalises to zeros
            continue
        rows = np.asarray(pool[layer][table[b]]).reshape(-1, LANES)
        np.testing.assert_allclose(out[b], _dense(q[b], rows, n), atol=2e-5)


@pytest.mark.parametrize("lengths", [[13, 0, 48, 9], [1, 8, 0, 0],
                                     [48, 48, 48, 48]],
                         ids=["ragged", "short-and-idle", "full-table"])
@pytest.mark.parametrize("layer,trip", [(0, TRIP_PAGES), (1, 4), (1, 1)])
def test_decode_kernel_against_jnp(lengths, layer, trip):
    """A slot's pages ``trip`` at a time: a trip that covers a whole row of
    the table, one that does not divide it, and one page a trip."""
    rng = np.random.default_rng(sum(lengths) + layer)
    pool = _pool(rng)
    B, pmax = 4, 6
    table = _table(rng, B, pmax)
    q = rng.standard_normal((B, HQ, LANES)).astype(np.float32)
    out = np.asarray(mla_decode_attention(
        jnp.asarray(q), pool, table, jnp.asarray(lengths, jnp.int32),
        layer, rank=RANK, scale=SCALE, interpret=True, trip=trip))
    assert out.shape == (B, HQ, RANK)
    _check(out, q, pool, layer, table, lengths)


#: TPU interpret mode: memory no one wrote reads NaN (so do the key blocks
#: and the accumulators before a call), a DMA lands when it is waited for,
#: and a read or write that races one is reported
POISONED = pltpu.InterpretParams(detect_races=True)


def _races_found() -> bool:
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    return interpret_pallas_call.races.races_found


@pytest.mark.parametrize("lengths,trip", [
    ([16, 24, 9], 2),           # spans of exactly T and T + 1 pages
    ([0, 0, 13, 21], 2),        # idle rows first
    ([13, 0, 0, 21], 2),        # ... between
    ([13, 21, 0, 0], 2),        # ... last
    ([0, 0, 0], 2),             # nothing to walk: no copy, no wait
    ([37], 2),                  # B = 1
    ([1], TRIP_PAGES),          # B = 1, a span of one page
    ([48, 3, 48, 0, 48], 1),    # more trips in flight than a row has
    ([9, 48, 0, 17, 1, 33], 4),
], ids=["T-and-T+1", "idle-first", "idle-between", "idle-last", "all-idle",
        "one-row", "one-page", "trip-1", "mixed"])
def test_decode_kernel_with_the_ring_poisoned(lengths, trip):
    """The rows of a key block past a span's end hold whatever was there: a
    NaN there, under a probability of zero, would be a NaN in the output.
    Pages outside every span are NaN too: the kernel copies none of them."""
    rng = np.random.default_rng(sum(lengths) + trip)
    B, pmax = len(lengths), 6
    pool = np.asarray(_pool(rng, pages=B * pmax + 1)).copy()
    table = np.arange(1, B * pmax + 1, dtype=np.int32).reshape(B, pmax)
    for b, n in enumerate(lengths):
        pool[:, table[b, -(-n // PAGE):]] = np.nan
    pool[:, 0] = np.nan
    q = rng.standard_normal((B, HQ, LANES)).astype(np.float32)
    out = np.asarray(mla_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(lengths, jnp.int32), 1, rank=RANK, scale=SCALE,
        interpret=POISONED, trip=trip))
    assert not _races_found()
    assert np.isfinite(out).all()
    _check(out, q, pool, 1, table, lengths)


@pytest.mark.parametrize("trip", [1, 4, 8])
def test_the_copies_a_call_starts_are_what_the_walked_counter_counts(
        counted_copies, trip):
    """The scheduler counts the pages the kernel walked from the host's
    length mirror by the kernel's own ``page_span``: sum over rows of (last -
    first + 1), of ``B x Pmax`` offered, a row that holds nothing counted as
    the one page its program costs. The kernel starts one copy for each of
    those pages (none for the row that holds nothing), and waits for each."""
    lengths = np.array([13, 0, 48, 9, 1, 17])
    table = jnp.asarray(np.arange(1, 37).reshape(6, 6), jnp.int32)
    first, last = page_span(lengths, PAGE, 6, None)
    walked = int((last - first + 1).sum())
    assert walked == 2 + 1 + 6 + 2 + 1 + 3
    assert walked / table.size == pytest.approx(15 / 36)
    pool = _pool(np.random.default_rng(0))
    seen = counted_copies(
        f"counted_{trip}", jnp.ones((6, HQ, LANES)), pool, table,
        jnp.asarray(lengths, jnp.int32), 0, rank=RANK, scale=SCALE,
        trip=trip)
    assert (seen["start"], seen["wait"]) == (walked - 1, walked - 1)
    # and a row's trips are the groups ``_count_attn_pages`` counts for
    # ``llm_attn_page_groups_total``, but for the row that holds nothing
    assert seen["trips"] == int(((last - first) // trip + 1).sum()) - 1
    blocks = {k: v for k, v in seen.items() if k.startswith("trips_of_")}
    assert blocks == {1: {"trips_of_1": 14},
                      4: {"trips_of_2": 4, "trips_of_4": 2},
                      8: {"trips_of_2": 3, "trips_of_4": 1,
                          "trips_of_8": 1}}[trip]


def test_trip_pages_is_what_the_scheduler_counts_groups_by():
    """``decode_page_group`` (what ``_count_attn_pages`` divides a row's
    span by for ``llm_attn_page_groups_total``) is the kernel's pages a
    trip: 16, and under one window for every layer no more than it spans."""
    from cyberfabric_core_tpu.models import get_config
    from cyberfabric_core_tpu.models.llama import decode_page_group

    assert (TRIP_PAGES, RING_BLOCKS) == (16, 3)
    assert trip_pages(64, None) == 16
    assert trip_pages(64, 128) == 3 and trip_pages(64, 4096) == 16
    assert trip_pages(16, 128) == 9 and trip_pages(16, 50) == 5
    # a trip is attended over as the smallest of these that holds its pages
    assert _block_sizes(16) == (2, 4, 8, 16)
    assert _block_sizes(3) == (2, 3) and _block_sizes(1) == (1,)
    kimi = get_config("kimi-k2.5-share32-15l")
    assert decode_page_group(kimi, 64, 48, 2, None) == trip_pages(64, None)
    motif = get_config("motif-3-beta-share32-27l")
    assert motif.window_pages(64) == trip_pages(64, motif.sliding_window)
    # the pair the group counter goes with counts the FULL layers' spans
    assert decode_page_group(motif, 64, 128, 2, None) == 16
    assert decode_page_group(motif, 64, 128, 2, motif.sliding_window) == 3
    # the K/V twin: the kernel's own rule by the shapes a shard sees
    from cyberfabric_core_tpu.ops.paged_attention import decode_trip_pages
    mistral = get_config("mistral-7b")
    assert decode_page_group(mistral, 64, 32, 2, mistral.sliding_window) == \
        decode_trip_pages(64, 8 * 128, 2, 32, mistral.sliding_window) == 16
    laguna = get_config("laguna-s-2.1-share8-12l")
    assert decode_page_group(laguna, 64, 128, 2, None) == 16
    assert decode_page_group(laguna, 64, 128, 2, laguna.sliding_window) == \
        laguna.window_pages(64) == 9


def _ragged_dense(q, rows, hist, qlen, window=None):
    """q [Hq, W, LANES], rows [S, LANES]: each query ``t < qlen`` at position
    ``hist + t`` over the keys it sees, in float64; zeros past the span."""
    W, S = q.shape[1], rows.shape[0]
    pos = hist + np.arange(W)[:, None]
    keys = np.arange(S)[None, :]
    seen = keys <= pos
    if window:
        seen &= keys > pos - window
    s = np.where(seen[None], (q.astype(np.float64) @ rows.T) * SCALE, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    out = (p / p.sum(-1, keepdims=True)) @ rows[:, :RANK]
    out[:, qlen:] = 0.0
    return out


def _ragged_case(name, trip):
    """(width, hist [3], q_lens [3]): three lanes, the middle one idle. The
    two cases named after a trip's edge place a lane's LAST key by the trip
    under test (``trip`` pages of PAGE keys)."""
    keys = trip * PAGE

    def ends(at, qlen):
        """The history behind ``qlen`` queries whose last key is key ``at``
        of a trip (0: its first), a trip or more in."""
        return (-(-qlen // keys) + 1) * keys + at + 1 - qlen

    return {
        "history-0": (16, [0, 0, 0], [16, 0, 9]),
        "history-off-page": (64, [5, 0, 11], [13, 0, 64]),
        # q-blocks past a span's end write zeros: one query, and 33 of 64
        "spans-shorter-than-the-chunk": (64, [21, 0, 40], [1, 0, 33]),
        # the last key is the first of a new trip / the last of a trip, for
        # the lane's last q-block and (second lane of each) for its first
        "one-key-into-a-trip": (64, [ends(0, 64), 0, ends(0, 20)],
                                [64, 0, 20]),
        "on-a-trips-last-key": (64, [ends(-1, 64), 0, ends(-1, 20)],
                                [64, 0, 20]),
        "width-512": (512, [3, 0, 70], [500, 0, 512]),
    }[name]


@pytest.mark.parametrize("trip", [1, 2, None], ids=["trip-1", "trip-2",
                                                    "shipped"])
@pytest.mark.parametrize("case", [
    "history-0", "history-off-page", "spans-shorter-than-the-chunk",
    "one-key-into-a-trip", "on-a-trips-last-key", "width-512"])
def test_ragged_kernel_against_jnp(case, trip):
    """A chunk's queries, head-major, each causal over its lane's history
    and the chunk itself, the pages of a q-block's span ``trip`` at a time;
    an idle lane between two busy ones and the padding past a span are
    zeros."""
    pages = trip or ragged_trip_pages(PAGE, None, 32)
    assert pages == (trip or 16)
    width, hist, qlens = _ragged_case(case, pages)
    assert min(hist) >= 0 and ragged_q_block(width) in (16, 32)
    pmax = -(-(max(hist) + width) // PAGE) + 1
    rng = np.random.default_rng(width + pages)
    pool = _pool(rng, pages=3 * pmax + 1)
    table = _table(rng, 3, pmax, pages=3 * pmax + 1)
    q = rng.standard_normal((3, HQ, width, LANES)).astype(np.float32)
    out = np.asarray(mla_ragged_attention(
        jnp.asarray(q), pool, table, jnp.asarray(hist), jnp.asarray(qlens),
        1, rank=RANK, scale=SCALE, interpret=True, trip=trip))
    assert out.shape == (3, HQ, width, RANK)
    assert not out[1].any()                               # the idle lane
    for r in (0, 2):
        rows = np.asarray(pool[1][table[r]]).reshape(-1, LANES)
        assert not out[r, :, qlens[r]:].any()
        np.testing.assert_allclose(
            out[r], _ragged_dense(q[r], rows.astype(np.float64), hist[r],
                                  qlens[r]), atol=2e-5)


@pytest.mark.parametrize("window,trip", [(None, None), (None, 2), (16, None),
                                         (16, 1), (40, 2)])
def test_ragged_kernel_with_the_ring_and_the_pool_poisoned(window, trip):
    """Every pool page outside the lanes' spans is NaN (the pages past a
    lane's last key, the pages left of its first query's window, a page no
    table names), and so are the ring's key blocks and the accumulators
    before the call: nothing outside a span is copied, nothing stale in a
    key block is attended over, and no access races a copy."""
    width, hist, qlens = 64, [37, 0, 150, 9], [64, 0, 40, 23]
    R, pmax = len(hist), 28
    rng = np.random.default_rng(7 + (window or 0))
    pool = np.asarray(_pool(rng, pages=R * pmax + 1)).copy()
    table = np.arange(1, R * pmax + 1, dtype=np.int32).reshape(R, pmax)
    live = pool.copy()
    pool[:, 0] = np.nan
    for r, (h, n) in enumerate(zip(hist, qlens)):
        first = max(h + 1 - window, 0) // PAGE if window and n else 0
        last = (h + n - 1) // PAGE if n else -1
        pool[:, table[r, :first]] = np.nan
        pool[:, table[r, last + 1:]] = np.nan
    q = rng.standard_normal((R, HQ, width, LANES)).astype(np.float32)
    out = np.asarray(mla_ragged_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(hist), jnp.asarray(qlens), 1, rank=RANK, scale=SCALE,
        interpret=POISONED, sliding_window=window, trip=trip))
    assert not _races_found()
    assert np.isfinite(out).all()
    for r in range(R):
        rows = live[1][table[r]].reshape(-1, LANES).astype(np.float64)
        np.testing.assert_allclose(
            out[r], _ragged_dense(q[r], rows, hist[r], qlens[r], window),
            atol=2e-5)


@pytest.mark.parametrize("window,trip", [(None, 1), (None, 2), (None, None),
                                         (16, None)])
def test_the_copies_a_ragged_call_starts_are_what_the_host_counts(
        counted_copies, window, trip):
    """The scheduler counts ``llm_ragged_pages_walked_total`` and
    ``llm_ragged_trips_total`` at a mixed step's dispatch from the lane's
    ``hist`` and ``q_lens`` by the kernel's own ``ragged_span``
    (``ragged_walk``): the kernel starts one copy for each of those pages,
    waits for each, and attends over that many key blocks; an idle lane and
    the q-blocks past a span's end cost neither."""
    width, hist, qlens, pmax = 64, [5, 0, 150, 64], [13, 0, 64, 33], 28
    pages, trips = ragged_walk(hist, qlens, width, PAGE, pmax, window,
                               trip=trip)
    if window is None:
        # lane 0: one q-block, keys 0..17, 3 pages; lane 2: q-blocks ending
        # at keys 181 and 213, 23 + 27 pages; lane 3: 96 and 97 keys, 12 + 13
        assert pages == 3 + (23 + 27) + (12 + 13)
        assert trips == {1: pages, 2: 2 + (12 + 14) + (6 + 7),
                         None: 1 + (2 + 2) + (1 + 1)}[trip]
    else:
        # a window of 16 keys over 32 queries spans 6-7 pages of 8 (lane 3's
        # second q-block is one query: 3), each one trip of 7
        assert (pages, trips) == (3 + (7 + 7) + (6 + 3), 5)
    pool = _pool(np.random.default_rng(0), pages=4 * pmax + 1)
    table = jnp.asarray(np.arange(1, 4 * pmax + 1).reshape(4, pmax),
                        jnp.int32)
    seen = counted_copies(
        f"counted_ragged_{window}_{trip}", jnp.ones((4, HQ, width, LANES)),
        pool, table, jnp.asarray(hist, jnp.int32),
        jnp.asarray(qlens, jnp.int32), 0, rank=RANK, scale=SCALE,
        sliding_window=window, trip=trip, kernel=mla_ragged_attention)
    assert (seen["start"], seen["wait"], seen["trips"]) == (pages, pages,
                                                            trips)


def test_ragged_trip_pages_is_a_key_block_of_full_tiles():
    """The pages a trip of the ragged kernel takes, from shapes: 256 keys
    (both dots at full 128-wide MXU tiles), no more than the decode kernel's
    16 pages, and under a window no more than a q-block's windows span, so a
    window layer's program is ONE trip."""
    assert ragged_trip_pages(64, None, 32) == 4
    assert ragged_trip_pages(64, 128, 32) == 4 == mla._window_pages(128, 64,
                                                                    32)
    assert ragged_trip_pages(64, 4096, 32) == 4
    assert ragged_trip_pages(16, None, 32) == 16
    assert ragged_trip_pages(8, None, 16) == TRIP_PAGES
    assert ragged_trip_pages(16, 128, 32) == 11
    assert _block_sizes(4) == (2, 4)
    from cyberfabric_core_tpu.models import get_config

    motif = get_config("motif-3-beta-share32-27l")
    assert ragged_trip_pages(64, motif.sliding_window, 32) \
        == motif.window_pages(64, 32)


def test_ragged_kernel_refuses_a_width_that_is_not_whole_blocks():
    pool = _pool(np.random.default_rng(0))
    with pytest.raises(ValueError, match="whole blocks"):
        mla_ragged_attention(
            jnp.zeros((1, HQ, 8, LANES)), pool, jnp.ones((1, 4), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), 0,
            rank=RANK, scale=SCALE, interpret=True)
