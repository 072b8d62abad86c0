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


def _select(scores, topk, span=None, **kw):
    """``dsa.select`` in interpret mode and the set it keeps: (the positions
    kept a row, count, k-th score, k-th position, the mask)."""
    scores = jnp.asarray(scores, jnp.float32)
    count, kth, kth_at = dsa.select(
        scores, topk, None if span is None else jnp.asarray(span),
        interpret=True, **kw)
    keep = dsa.keep_mask(scores, kth, kth_at)
    return (np.asarray(dsa.chosen_positions(keep, topk)), np.asarray(count),
            np.asarray(kth), np.asarray(kth_at), np.asarray(keep))


def test_select_is_exact_counts_and_breaks_ties_by_position():
    """The top-k is ``lax.top_k``'s: exact, equal scores by rising position;
    a query that sees fewer keys than ``topk`` keeps them all and pads with
    -1; ``keep_mask`` is the same set as a mask, ``chosen_positions`` the
    same set as a list."""
    scores = np.full((4, 24), dsa.NEG, np.float32)
    rng = np.random.default_rng(4)
    scores[0, :20] = rng.normal(size=20)
    scores[1, :5] = rng.normal(size=5)           # sees 5 < topk
    scores[2, :20] = 1.0                         # all equal: the first 8
    picked, count, kth, kth_at, keep = _select(scores, 8)
    assert count.tolist() == [8, 5, 8, 0]
    assert set(picked[0]) == set(np.argsort(-scores[0, :20])[:8])
    assert kth[0] == np.sort(scores[0, :20])[-8] and kth[1] == dsa.NEG
    assert picked[1].tolist() == [0, 1, 2, 3, 4, -1, -1, -1]
    assert picked[2].tolist() == list(range(8)) and kth_at[2] == 7
    assert (picked[3] == -1).all() and kth_at[3] == -1
    assert keep.sum(axis=1).tolist() == [8, 5, 8, 0]
    for r in range(4):
        assert set(np.flatnonzero(keep[r])) == set(picked[r][picked[r] >= 0])
    # under a span no round reads a key past it: the same answer
    wide = np.full((3, 256), dsa.NEG, np.float32)
    wide[:, :70] = rng.normal(size=(3, 70))
    whole = _select(wide, 8)
    for span in (70, 129, 256):
        for a, b in zip(whole, _select(wide, 8, span)):
            np.testing.assert_array_equal(a, b)


def _top_k_keeps(scores, topk):
    """(count, mask) of ``lax.top_k`` + ``keep_mask``: what ``select`` did
    while it sorted."""
    scores = jnp.asarray(scores, jnp.float32)
    values, positions = jax.lax.top_k(scores, min(topk, scores.shape[-1]))
    seen = values > dsa.NEG / 2
    positions = jnp.where(seen, positions, -1)
    return (np.asarray(seen.sum(-1)),
            np.asarray(dsa.keep_mask(scores, values[..., -1],
                                     positions[..., -1])))


def _select_cases():
    rng = np.random.default_rng(9)
    S = 1024
    plain = rng.normal(size=(40, S)).astype(np.float32)
    cut = plain.copy()
    for r, n in enumerate(rng.integers(0, S, 40)):
        cut[r, n:] = dsa.NEG
    few = np.full((8, S), dsa.NEG, np.float32)
    few[:, :30] = rng.normal(size=(8, 30))
    few[3] = dsa.NEG                                  # a row that sees none
    inside = np.full((5, 2048), dsa.NEG, np.float32)
    inside[:, :700] = rng.normal(size=(5, 700))
    return {
        "random rows": (plain, 64, None, {}),
        "many ties": (rng.integers(-3, 4, (16, S)), 100, None, {}),
        "an all-equal row": (np.ones((8, S)), 100, None, {}),
        "rows cut by NEG": (cut, 64, S, {}),
        "rows that see under k and none": (few, 64, 30, {}),
        "negative scores": (-np.abs(plain), 64, None, {}),
        "N not a whole block": (plain[:13], 64, None, {"rows": 8}),
        "blocks of 8 rows, steps of 128 lanes": (plain, 64, None,
                                                 {"rows": 8, "lanes": 128}),
        "a span that ends inside a lane block": (inside, 64, 700, {}),
        "a width of no whole lane tile": (plain[:4, :160], 12, None, {}),
        "topk over the width": (plain[:4, :160], 200, None, {}),
    }


@pytest.mark.parametrize("case", list(_select_cases()))
def test_dsa_select_keeps_what_top_k_keeps(case):
    """The bisection kernel against the sort it replaced: the same count and
    the same mask, key for key, ties to the lower position."""
    scores, topk, span, kw = _select_cases()[case]
    _, count, _, _, keep = _select(scores, topk, span, **kw)
    want_count, want_keep = _top_k_keeps(scores, topk)
    np.testing.assert_array_equal(count, want_count)
    np.testing.assert_array_equal(keep, want_keep)


def test_dsa_select_keeps_exactly_k_among_zeros_of_both_signs():
    """A k-th score that is a zero among zeros of both signs: IEEE compares
    them equal, as ``keep_mask`` does, so exactly ``topk`` are kept, the
    zeros among them by rising position whatever their sign (the sort ordered
    ``-0.0`` under ``+0.0`` and kept more)."""
    rng = np.random.default_rng(10)
    scores = np.where(rng.random((8, 1024)) < 0.5, 0.0, -0.0).astype(
        np.float32)
    scores[:, ::7], scores[:, ::11] = -1.0, 2.0
    picked, count, kth, _, keep = _select(scores, 200)
    assert (count == 200).all() and (keep.sum(-1) == 200).all()
    assert (kth == 0).all()
    for r in range(8):
        twos, zeros = np.flatnonzero(scores[r] == 2), np.flatnonzero(
            scores[r] == 0)
        want = np.sort(np.concatenate([twos, zeros[: 200 - len(twos)]]))
        assert picked[r].tolist() == want.tolist()


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
    picked, _, _, _, keep = _select(scores, topk)
    keep = jnp.asarray(keep)
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
