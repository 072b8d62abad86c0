"""The latent attention kernels (ops/mla_attention.py) in interpret mode
against plain ``jnp``: ragged lengths, idle rows, a page read once as key
(all its lanes) and as value (its first ``rank``); and the work list they
share with the K/V decode kernel feeding the walked/offered counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.ops.mla_attention import (PAGE_GROUP,
                                                    latent_work_list,
                                                    mla_decode_attention,
                                                    mla_ragged_attention,
                                                    ragged_q_block)
from cyberfabric_core_tpu.ops.paged_attention import (decode_work_list,
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


@pytest.mark.parametrize("lengths", [[13, 0, 48, 9], [1, 8, 0, 0],
                                     [48, 48, 48, 48]],
                         ids=["ragged", "short-and-idle", "full-table"])
@pytest.mark.parametrize("layer,group", [(0, PAGE_GROUP), (1, 4), (1, 1)])
def test_decode_kernel_against_jnp(lengths, layer, group):
    """A slot's pages ``group`` at a time: one that covers a whole row of
    the table, one that does not divide it, and one page a program."""
    rng = np.random.default_rng(sum(lengths) + layer)
    pool = _pool(rng)
    B, pmax = 4, 6
    table = jnp.asarray(rng.permutation(np.arange(1, 40))[: B * pmax]
                        .reshape(B, pmax), jnp.int32)
    q = rng.standard_normal((B, HQ, LANES)).astype(np.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    out = np.asarray(mla_decode_attention(
        jnp.asarray(q), pool, latent_work_list(table, lens, PAGE, group),
        layer, rank=RANK, scale=SCALE, interpret=True))
    assert out.shape == (B, HQ, RANK)
    for b, n in enumerate(lengths):
        if n == 0:
            assert not out[b].any()          # an idle row finalises to zeros
            continue
        rows = np.asarray(pool[layer][table[b]]).reshape(-1, LANES)
        np.testing.assert_allclose(out[b], _dense(q[b], rows, n), atol=2e-5)


@pytest.mark.parametrize("width", [16, 64])
def test_ragged_kernel_against_jnp(width):
    """A chunk's queries, head-major, each causal over its lane's history
    and the chunk itself; an idle lane and the padding past a span are
    zeros."""
    rng = np.random.default_rng(width)
    pool = _pool(rng)
    table = jnp.asarray([[3, 5, 7, 9, 11, 13, 15, 17, 19, 21],
                         [2, 4, 6, 8, 10, 12, 14, 16, 18, 20],
                         [1, 22, 23, 24, 25, 26, 27, 28, 29, 30]], jnp.int32)
    hist = np.array([5, 0, 11])
    qlens = np.array([min(width, 13), 0, width])
    assert ragged_q_block(width) in (16, 32)
    q = rng.standard_normal((3, HQ, width, LANES)).astype(np.float32)
    out = np.asarray(mla_ragged_attention(
        jnp.asarray(q), pool, table, jnp.asarray(hist), jnp.asarray(qlens),
        1, rank=RANK, scale=SCALE, interpret=True))
    assert out.shape == (3, HQ, width, RANK)
    for r in range(3):
        rows = np.asarray(pool[1][table[r]]).reshape(-1, LANES)
        for t in range(width):
            if t >= qlens[r]:
                assert not out[r, :, t].any()
                continue
            np.testing.assert_allclose(
                out[r, :, t], _dense(q[r, :, t], rows, hist[r] + t + 1),
                atol=2e-5)


def test_ragged_kernel_refuses_a_width_that_is_not_whole_blocks():
    pool = _pool(np.random.default_rng(0))
    with pytest.raises(ValueError, match="whole blocks"):
        mla_ragged_attention(
            jnp.zeros((1, HQ, 8, LANES)), pool, jnp.ones((1, 4), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), 0,
            rank=RANK, scale=SCALE, interpret=True)


def test_work_list_is_what_the_walked_counter_counts():
    """The latent kernel's grid is ``decode_work_list``'s pages in use, a
    slot's pages a group at a time, and the scheduler counts those pages
    from the host's length mirror by the same ``page_span``: pages walked ==
    sum over rows of (last - first + 1), of ``B x Pmax`` offered."""
    lengths = np.array([13, 0, 48, 9, 1, 17])
    table = jnp.asarray(np.arange(1, 37).reshape(6, 6), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    pages = decode_work_list(table, lens, PAGE)
    first, last = page_span(lengths, PAGE, 6, None)
    walked = int((last - first + 1).sum())
    assert int(pages.n_items) == walked == 2 + 1 + 6 + 2 + 1 + 3
    assert walked / table.size == pytest.approx(15 / 36)
    for group in (1, 4, 8):
        work = latent_work_list(table, lens, PAGE, group)
        n = int(work.n_items)
        assert n == sum(int(l) // group + 1 for l in last)
        phys = np.asarray(work.phys).reshape(-1, group)[:n]
        rows, firsts = np.asarray(work.row)[:n], np.asarray(work.first)[:n]
        seen = []
        for r, f, ph in zip(rows, firsts, phys):
            for g in range(group):
                logical = min(f + g, int(last[r]))   # past the last: it again
                assert ph[g] == int(table[r, logical])
                if f + g <= last[r]:
                    seen.append((int(r), int(f + g)))
        # every page that holds tokens once, rows in order, pages ascending
        assert seen == [(r, p) for r in range(6)
                        for p in range(int(last[r]) + 1)]
        assert len(seen) == walked
