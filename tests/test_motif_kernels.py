"""The latent kernels (ops/mla_attention.py) under a window, in interpret
mode at motif's head count: 80 query rows (five sublane tiles, not a power of
two), window 128, page 64, at the lengths where a window's span changes its
page count (127, 128, 129, 191, 192, 193), in the middle (700) and past a
table of 64 pages (4097), against a dense softmax over the visible keys. The
pages left of a window's span hold NaN and the table names the (NaN) scratch
page there, so a kernel that read one would say so. Without a window the
decode kernel is PR 47's, bit for bit, and so is the ragged kernel at a page
a trip (its own trip of 4 pages sums in another order)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config
from cyberfabric_core_tpu.models.llama import decode_work
from cyberfabric_core_tpu.ops import mla_attention as mla
from cyberfabric_core_tpu.ops.paged_attention import page_span

HQ, WINDOW, PAGE, RANK, LANES = 80, 128, 64, 128, 256
LENGTHS = (127, 128, 129, 191, 192, 193, 700, 4097)
SCALE = 0.09


def _dense(q, rows, positions, t, window):
    """[Hq, RANK]: softmax over the keys ``rows`` [T, LANES] the query at
    ``t`` sees."""
    seen = positions <= t
    if window:
        seen &= positions > t - window
    s = (q.astype(np.float32) @ rows.astype(np.float32).T) * SCALE
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    return p @ rows[:, :RANK].astype(np.float32)


def _pool(length, window, seed):
    """A row of ``length`` tokens in a pool of its own pages; under a window
    the pages left of the last query's span are NaN and off the table."""
    n = -(-length // PAGE)
    rows = np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (n * PAGE, LANES), jnp.bfloat16))
    pool = np.full((1, n + 2, PAGE, LANES), np.nan, np.float32)
    pool[0, 1: n + 1] = rows.astype(np.float32).reshape(n, PAGE, LANES)
    table = np.zeros((2, n + 1), np.int32)
    table[0, :n] = 1 + np.arange(n)
    table[1, 0] = n + 1                                  # an empty row's
    pool[0, n + 1] = 0.0
    return rows, pool, table


@pytest.mark.parametrize("window", [WINDOW, None], ids=["window", "full"])
@pytest.mark.parametrize("length", LENGTHS)
def test_decode_kernel_at_80_heads(length, window):
    rows, pool, table = _pool(length, window, length)
    if window:      # what the pool has given back
        dead = max(length - window, 0) // PAGE
        pool[0, 1: dead + 1] = np.nan
        table[0, :dead] = 0
    else:
        pool[0, 0] = 0.0
    q = jax.random.normal(jax.random.PRNGKey(1), (2, HQ, LANES), jnp.bfloat16)
    lengths = jnp.asarray([length, 0], jnp.int32)
    # a window's trip is the pages it spans: one trip a row at any length
    assert mla.trip_pages(PAGE, window) == (3 if window else mla.TRIP_PAGES)
    out = mla.mla_decode_attention(
        q, jnp.asarray(pool, jnp.bfloat16), jnp.asarray(table), lengths, 0,
        rank=RANK, scale=SCALE, interpret=True, sliding_window=window,
        name="gdla_test")
    want = _dense(np.asarray(q[0]), rows[:length], np.arange(length),
                  length - 1, window)
    got = np.asarray(out[0].astype(jnp.float32))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=0.02, rtol=0.02)


@pytest.mark.parametrize("trip", [1, None], ids=["trip-1", "shipped"])
@pytest.mark.parametrize("window", [WINDOW, None], ids=["window", "full"])
@pytest.mark.parametrize("length", LENGTHS)
def test_ragged_kernel_at_80_heads(length, window, trip):
    """A chunk of 40 queries that ends at ``length`` (two q-blocks of a
    64-wide lane, the second ragged), a page a trip as the parent's grid
    took them and at the shipped trip: 4 pages, a window's q-block ONE
    trip."""
    assert mla.ragged_trip_pages(PAGE, window, 32) == 4
    qlen = 40
    hist = length - qlen
    rows, pool, table = _pool(length, window, 100 + length)
    if window:
        dead = max(hist + 1 - window, 0) // PAGE
        pool[0, 1: dead + 1] = np.nan
        table[0, :dead] = 0
    else:
        pool[0, 0] = 0.0
    q = jax.random.normal(jax.random.PRNGKey(2), (1, HQ, 64, LANES),
                          jnp.bfloat16)
    out = mla.mla_ragged_attention(
        q, jnp.asarray(pool, jnp.bfloat16), jnp.asarray(table[:1]),
        jnp.asarray([hist], jnp.int32), jnp.asarray([qlen], jnp.int32), 0,
        rank=RANK, scale=SCALE, interpret=True, sliding_window=window,
        trip=trip)
    got = np.asarray(out[0].astype(jnp.float32))          # [Hq, 64, RANK]
    assert np.isfinite(got[:, :qlen]).all()
    for i in (0, 23, 31, 32, qlen - 1):
        want = _dense(np.asarray(q[0, :, i]), rows[:length],
                      np.arange(length), hist + i, window)
        np.testing.assert_allclose(got[:, i], want, atol=0.02, rtol=0.02)


def test_a_window_layers_copies_do_not_grow_with_context(counted_copies):
    """A window layer's row is ONE trip of the pages a window spans (3) at
    any length, and the kernel starts a copy for the pages of the span alone:
    a row of 8 191 tokens starts 3 (of 8 192, whose window starts on a page:
    2), where a full layer's starts 128 in 8 trips; a ragged program's ONE
    trip is the pages a q-block's windows span (4), whatever the table's.
    ``decode_work`` hands a kernel the table and the lengths: it walks them
    itself."""
    cfg = get_config("motif-3-beta-share32-27l")
    assert cfg.window_pages(PAGE) == 3 and cfg.window_pages(PAGE, 32) == 4
    assert mla.trip_pages(PAGE, cfg.sliding_window) == 3
    pool = jnp.zeros((1, 4, PAGE, 128), jnp.bfloat16)
    table = jnp.ones((5, 128), jnp.int32)
    lengths = jnp.asarray([1, 129, 4097, 8191, 8192], jnp.int32)
    work = decode_work(table, lengths)      # the same for both kinds
    assert work[0] is table and work[1] is lengths
    q = jnp.zeros((5, 8, 128), jnp.bfloat16)
    first, last = page_span(np.asarray(lengths), PAGE, 128,
                            cfg.sliding_window)
    np.testing.assert_array_equal(first, [0, 0, 62, 125, 126])
    np.testing.assert_array_equal(last - first + 1, [1, 3, 3, 3, 2])
    seen = counted_copies("counted_window", q, pool, table, lengths, 0,
                          rank=64, scale=SCALE,
                          sliding_window=cfg.sliding_window)
    assert (seen["start"], seen["wait"], seen["trips"]) == (12, 12, 5)
    # a window of two pages is attended over as two, not three
    assert (seen["trips_of_2"], seen["trips_of_3"]) == (2, 3)
    seen = counted_copies("counted_full", q, pool, table, lengths, 0, rank=64,
               scale=SCALE)
    assert (seen["start"], seen["wait"]) == (325, 325)
    assert seen["trips"] == 1 + 1 + 5 + 8 + 8


def _digest(x):
    return hashlib.sha256(
        np.asarray(x.astype(jnp.float32)).tobytes()).hexdigest()


def _parent_inputs():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    pool = jax.random.normal(ks[0], (2, 9, 16, 128), jnp.bfloat16)
    q = jax.random.normal(ks[1], (3, 8, 128), jnp.bfloat16)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]], jnp.int32)
    rq = jax.random.normal(ks[2], (2, 8, 32, 128), jnp.bfloat16)
    return pool, q, table, rq


#: sha256 of the parent commit's kernels' outputs on ``_parent_inputs`` (PR
#: 47, interpret mode on the CPU backend)
PARENT = {
    "decode1": "6c2e2a433b2ddd75489778d79ad7bf03e71fa349b9d0ae64f596fd999c40d17c",
    "decode8": "853e84230567611afc29b371b2daf915076a944a0fb7deac46a03cf2156963ec",
    "ragged": "b6470649734452e7e0cb4c9fa54e3a2f73a1907987402a9d6ce7e13ae7a04088",
}


@pytest.mark.parametrize("group", [1, 8])
def test_the_unwindowed_decode_kernel_is_the_parents_bit_for_bit(group):
    pool, q, table, _ = _parent_inputs()
    out = mla.mla_decode_attention(
        q, pool, table, jnp.asarray([40, 17, 0], jnp.int32), 1, rank=96,
        scale=0.11, interpret=True, trip=group)
    assert _digest(out[:2]) == PARENT[f"decode{group}"]


def test_the_unwindowed_ragged_kernel_is_the_parents_bit_for_bit():
    pool, _, table, rq = _parent_inputs()
    out = mla.mla_ragged_attention(
        rq, pool, table[:2], jnp.asarray([9, 0], jnp.int32),
        jnp.asarray([32, 20], jnp.int32), 1, rank=96, scale=0.11,
        interpret=True, trip=1)
    assert _digest(out) == PARENT["ragged"]
