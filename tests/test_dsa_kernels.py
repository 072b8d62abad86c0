"""``ops/dsa.py`` against ``jax.numpy`` in interpret mode: the index pass of
decode rows and of a chunk's queries over a shuffled page table, the exact
top-k and its mask, and the one operand either latent kernel takes for the
keys each query chose."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops import dsa
from cyberfabric_core_tpu.ops.mla_attention import (mla_decode_attention,
                                                    mla_ragged_attention)

PAGE, PMAX, HI, LANES = 4, 40, 4, 128      # three trips of 16 pages a row
S = PAGE * PMAX


def _pools(rows, seed=0, layers=2, lanes=LANES):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(layers, rows * PMAX + 1, PAGE, lanes)),
                       jnp.bfloat16)
    table = 1 + rng.permutation(rows * PMAX).astype(np.int32).reshape(
        rows, PMAX)
    return pool, jnp.asarray(table)


def _keys(pool, table, layer):
    """A row's keys in order, float32: [rows, S, lanes]."""
    return pool[layer][table].reshape(table.shape[0], S, -1).astype(
        jnp.float32)


def _plain_scores(q, w, keys):
    """``sum_h w_h relu(q_h . k)``: q [.., H, D], w [.., H], keys [S, D]."""
    dots = jnp.einsum("...hd,sd->...hs", q.astype(jnp.float32), keys,
                      precision="highest")
    return jnp.einsum("...hs,...h->...s", jnp.maximum(dots, 0), w,
                      precision="highest")


def test_index_scores_of_decode_rows_match_plain_numpy():
    """Rows of 1, 17, 64, 65 and 160 tokens and an empty one: every key a
    row holds is scored by all heads, relu'd, weighed and summed; the keys
    past its length, and an empty row's, read ``NEG``; layer 1 is read, not
    layer 0."""
    lengths = np.array([1, 17, 64, 0, 65, 160], np.int32)
    B = len(lengths)
    pool, table = _pools(B)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, HI, LANES)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(B, HI)), jnp.float32)
    got = np.asarray(dsa.index_scores(q, w, pool, table, jnp.asarray(lengths),
                                      1, interpret=True))
    assert got.shape == (B, S)
    keys = _keys(pool, table, 1)
    for b, n in enumerate(lengths):
        want = np.asarray(_plain_scores(q[b], w[b], keys[b]))
        np.testing.assert_allclose(got[b, :n], want[:n], rtol=1e-4, atol=1e-3)
        assert (got[b, n:] == dsa.NEG).all()
    other = np.asarray(dsa.index_scores(q, w, pool, table,
                                        jnp.asarray(lengths), 0,
                                        interpret=True))
    assert np.abs(other[5, :160] - got[5, :160]).max() > 0.1


@pytest.mark.parametrize("width", [16, 32])
def test_index_scores_of_a_chunk_match_plain_numpy(width):
    """Two lanes of a chunk (one behind 37 tokens of history, one fresh and
    shorter than the chunk) and an idle lane: a query at ``hist + qi``
    scores the keys up to itself; everything else reads ``NEG``."""
    hist = np.array([37, 0, 9], np.int32)
    q_lens = np.array([width, width - 5, 0], np.int32)
    R = len(hist)
    pool, table = _pools(R, seed=2)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(R, width, HI, LANES)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(R, width, HI)), jnp.float32)
    got = np.asarray(dsa.index_scores_ragged(
        q, w, pool, table, jnp.asarray(hist), jnp.asarray(q_lens), 1,
        interpret=True))
    assert got.shape == (R, width, S)
    keys = _keys(pool, table, 1)
    for r in range(R):
        want = np.asarray(_plain_scores(q[r], w[r], keys[r]))
        for qi in range(width):
            seen = hist[r] + qi + 1 if qi < q_lens[r] else 0
            np.testing.assert_allclose(got[r, qi, :seen], want[qi, :seen],
                                       rtol=1e-4, atol=1e-3)
            assert (got[r, qi, seen:] == dsa.NEG).all()


def test_select_is_exact_counts_and_breaks_ties_by_position():
    """The top-k is ``lax.top_k``'s: exact, by falling score, equal scores by
    rising position; a query that sees fewer keys than ``topk`` keeps them
    all and pads with -1; ``keep_mask`` is the same set as a mask."""
    scores = np.full((4, 24), dsa.NEG, np.float32)
    rng = np.random.default_rng(4)
    scores[0, :20] = rng.normal(size=20)
    scores[1, :5] = rng.normal(size=5)           # sees 5 < topk
    scores[2, :20] = 1.0                         # all equal: the first 8
    picked, count, kth, kth_at = dsa.select(jnp.asarray(scores), 8)
    picked, count = np.asarray(picked), np.asarray(count)
    assert count.tolist() == [8, 5, 8, 0]
    assert set(picked[0]) == set(np.argsort(-scores[0, :20])[:8])
    assert sorted(picked[1][:5]) == [0, 1, 2, 3, 4] and (picked[1][5:] == -1).all()
    assert picked[2].tolist() == list(range(8))
    assert (picked[3] == -1).all()
    keep = np.asarray(dsa.keep_mask(jnp.asarray(scores), kth, kth_at))
    assert keep.sum(axis=1).tolist() == [8, 5, 8, 0]
    for r in range(4):
        assert set(np.flatnonzero(keep[r])) == set(picked[r][picked[r] >= 0])
    # under a span the sort is over a prefix that holds it: the same answer
    wide = np.full((3, 256), dsa.NEG, np.float32)
    wide[:, :70] = rng.normal(size=(3, 70))
    assert dsa._prefixes(256, 8) == (64, 128, 256)
    assert dsa._prefixes(16384, 2048) == (4096, 8192, 16384)
    whole = dsa.select(jnp.asarray(wide), 8)
    for span in (70, 64, 129, 256):
        if span < 70:
            continue
        part = dsa.select(jnp.asarray(wide), 8, span=jnp.asarray(span))
        for a, b in zip(whole, part):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_decode_kernel_attends_the_chosen_rows_and_no_others():
    """Under ``keep`` the latent decode kernel gives what a softmax over
    exactly the chosen rows gives (against plain numpy); a row that sees no
    more than ``topk`` keys keeps them all and reads as the kernel without
    the operand does, bit for bit; an empty row gives zeros."""
    rank, lanes, Hq, topk = 32, 128, 4, 12
    lengths = np.array([40, 9, 12, 0, 77], np.int32)
    B = len(lengths)
    pool, table = _pools(B, seed=5, lanes=lanes)
    pool = pool.at[..., 48:].set(0)
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(B, Hq, lanes)) * 0.3, jnp.bfloat16
                    ).at[..., 48:].set(0)
    scores = np.full((B, S), dsa.NEG, np.float32)
    for b, n in enumerate(lengths):
        scores[b, :n] = rng.normal(size=n)
    picked, _, kth, kth_at = dsa.select(jnp.asarray(scores), topk)
    keep = dsa.keep_mask(jnp.asarray(scores), kth, kth_at)
    args = (q, pool, table, jnp.asarray(lengths), 1)
    kw = dict(rank=rank, scale=0.2, interpret=True)
    got = np.asarray(mla_decode_attention(*args, **kw, keep=keep), np.float32)
    rows = _keys(pool, table, 1)
    for b, n in enumerate(lengths):
        if not n:
            assert not got[b].any()
            continue
        chosen = np.asarray(picked[b])
        chosen = chosen[chosen >= 0]
        assert len(chosen) == min(n, topk)
        assert set(chosen) == set(np.flatnonzero(np.asarray(keep[b])))
        k = np.asarray(rows[b])[chosen]                       # [n, lanes]
        s = np.asarray(q[b], np.float32) @ k.T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ k[:, :rank]
        np.testing.assert_allclose(got[b], want, rtol=0.03, atol=0.03)
    dense = np.asarray(mla_decode_attention(*args, **kw), np.float32)
    for b in (1, 2):                   # rows under topk are attended whole
        np.testing.assert_array_equal(got[b], dense[b])
    assert np.abs(got[0] - dense[0]).max() > 0.05


@pytest.mark.parametrize("trip", [None, 1])
def test_the_ragged_kernel_attends_the_kept_keys_and_no_others(trip):
    """``keep`` of all ones is the kernel without the operand, bit for bit;
    a mask that leaves keys out gives the softmax over the kept visible keys
    alone, for a lane behind history, a fresh short lane and an idle one."""
    rank, lanes, Hq, width = 32, 128, 4, 16
    hist = np.array([23, 0, 5], np.int32)
    q_lens = np.array([16, 11, 0], np.int32)
    R = len(hist)
    pool, table = _pools(R, seed=7, lanes=lanes)
    pool = pool.at[..., 48:].set(0)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(R, Hq, width, lanes)) * 0.3,
                    jnp.bfloat16).at[..., 48:].set(0)
    args = (q, pool, table, jnp.asarray(hist), jnp.asarray(q_lens), 1)
    kw = dict(rank=rank, scale=0.2, interpret=True, trip=trip)
    plain = np.asarray(mla_ragged_attention(*args, **kw), np.float32)
    ones = np.asarray(mla_ragged_attention(
        *args, **kw, keep=jnp.ones((R, width, S), jnp.int8)), np.float32)
    np.testing.assert_array_equal(plain, ones)
    keep = (rng.random((R, width, S)) < 0.4).astype(np.int8)
    keep[np.arange(R)[:, None], np.arange(width)[None, :],
         hist[:, None] + np.arange(width)[None, :]] = 1   # its own key
    got = np.asarray(mla_ragged_attention(*args, **kw,
                                          keep=jnp.asarray(keep)), np.float32)
    rows = np.asarray(_keys(pool, table, 1))
    for r in range(R):
        for qi in range(width):
            if qi >= q_lens[r]:
                assert not got[r, :, qi].any()
                continue
            seen = np.flatnonzero(keep[r, qi, : hist[r] + qi + 1])
            k = rows[r][seen]
            s = np.asarray(q[r, :, qi], np.float32) @ k.T * 0.2
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ k[:, :rank]
            np.testing.assert_allclose(got[r, :, qi], want, rtol=0.03,
                                       atol=0.03)
    assert np.abs(got[0] - plain[0]).max() > 0.05
