"""The kernels solar_open2 runs, in interpret mode at its shapes' RATIOS (tiny
sizes): the KDA state kernel against its ``jax.numpy`` twin and against the
recurrence, masked rows bit for bit; the chunked WY form against the
recurrence token by token, from a non-zero state, ragged, with decays strong
enough that the factorised form would overflow float32; the tiles the shared
rules give its shapes; the grouped matmul on a share of many experts; both
paged kernels at 8 queries a kv head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config, llama
from cyberfabric_core_tpu.ops import kda, ssd
from cyberfabric_core_tpu.ops.grouped_matmul import (BLOCK_BYTES, _col_tile,
                                                     group_items, row_tile)
from cyberfabric_core_tpu.ops.paged_attention import (
    decode_trip_pages, paged_decode_attention, ragged_paged_attention)

B, H, K, V = 3, 8, 8, 16            # keys narrower than values: a transposed
                                    # state would not even have the shape


def _inputs(T, seed, strongest=16.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    return {"q": unit(jax.random.normal(k[0], (B, T, H, K))) * K ** -0.5,
            "k": unit(jax.random.normal(k[1], (B, T, H, K))),
            "v": jax.random.normal(k[2], (B, T, H, V)),
            "g": -jax.random.uniform(k[3], (B, T, H, K), minval=1e-3,
                                     maxval=strongest),
            "beta": 2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (B, T, H))),
            "state": jax.random.normal(k[5], (B, H, K, V))}


def _scan(inp, upto=None):
    return kda.kda_scan(*(inp[n][:, :upto] for n in "qkvg"),
                        inp["beta"][:, :upto], inp["state"])


def test_the_tiles_follow_from_the_shapes_and_the_others_keep_theirs():
    """No tile rule changed for this model. 64 heads of [128, 128] f32 are 16
    a program (1 MB, ``_head_block``'s budget; granite 32, nemotron two
    whole groups of 16 since PR 46, falcon 8). An expert's 4096 x 1280 int8 matrix is 5.2 MB, over the 4 MB
    block: the rule there is gives it TWO column tiles of 640, and 1280 x
    4096 two of 2048 (ISSUE 45 expected none; kimi's 7168 x 2048 keeps its
    512, granite's and sdar's stay whole). A decode step's 512 assignments
    are a capacity of 256 rows over 40 held experts, in row tiles of 64; a
    mixed step of 512 tokens beside 64 decode rows 2 304 of 4 608, in 64s
    too. A page row of 8 kv heads takes 4 pages a program, as mistral's."""
    assert ssd._head_block(64, 1, 4 * 128 * 128) == 16          # solar-open2
    assert ssd._head_block(128, 8, 4 * 64 * 128) == 32          # nemotron
    assert ssd._head_block(128, 1, 4 * 64 * 128) == 32          # granite
    assert ssd._head_block(32, 2, 4 * 128 * 256) == 8           # falcon-h1
    assert 4096 * 1280 > BLOCK_BYTES
    assert _col_tile(4096, 1280, 1) == 640
    assert _col_tile(1280, 4096, 1) == 2048
    assert _col_tile(7168, 2048, 1) == 512                      # kimi
    assert _col_tile(4096, 768, 1) == 768                       # granite
    assert _col_tile(1024, 2688, 1) == 2688                     # nemotron
    cfg = get_config("solar-open2-share8-12l")
    assert llama.moe_capacity(64 * 8, cfg) == 256
    assert llama.moe_capacity(576 * 8, cfg) == 2304
    assert row_tile(256, 40) == 64 and row_tile(2304, 40) == 64
    items, *_ = group_items(jnp.zeros((40,), jnp.int32), 256, 64)
    assert items.shape == (256 // 64 + 39,)
    assert decode_trip_pages(64, 8 * 128, 2, 48, None) == 16    # solar-open2
    assert decode_trip_pages(64, 8 * 128, 2, 32, 4096) == 16    # mistral
    assert llama.decode_page_group(cfg, 64, 48, 2, None) == 16


def test_state_kernel_equals_its_twin_and_keeps_masked_rows_bit_for_bit():
    """Layer 1 of a two-layer slab of four rows, a batch of three of which
    row 1 is masked: the kernel (interpret mode) against the ``jax.numpy``
    step, against the recurrence itself, and every row it must not touch
    bitwise: the masked row, the other layer, the row beyond the batch."""
    inp = _inputs(1, seed=5)
    assert ssd._head_block(H, 1, 4 * K * V) == H        # one program a row
    slab = jnp.stack([inp["state"] * 0.5, inp["state"]])
    slab = jnp.concatenate([slab, slab[:, :1] + 1.0], axis=1)   # a 4th row
    mask = jnp.asarray([True, False, True])
    args = (slab, jnp.int32(1), *(inp[n][:, 0] for n in "qkvg"),
            inp["beta"][:, 0], mask)
    o_j, s_j = kda.kda_state_update(*args, kernel=False)
    o_k, s_k = kda.kda_state_update(*args, kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_j), atol=2e-6)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j), atol=2e-6)
    o_r, s_r = _scan(inp)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r[:, 0]),
                               atol=2e-6)
    for row in (0, 2):
        np.testing.assert_allclose(np.asarray(s_k[1, row]),
                                   np.asarray(s_r[row]), atol=2e-6)
        assert not np.array_equal(np.asarray(s_k[1, row]),
                                  np.asarray(slab[1, row]))
    for twin in (s_k, s_j):
        assert np.array_equal(np.asarray(twin[1, 1]), np.asarray(slab[1, 1]))
        assert np.array_equal(np.asarray(twin[0]), np.asarray(slab[0]))
        assert np.array_equal(np.asarray(twin[1, 3]), np.asarray(slab[1, 3]))


def test_state_kernel_at_several_head_blocks(monkeypatch):
    """More heads than a program takes: a row is several programs, each on
    its own heads' columns of q, k, βk and the decays."""
    monkeypatch.setattr(ssd, "_STATE_BLOCK_BYTES", 2 * 4 * K * V)
    assert ssd._head_block(H, 1, 4 * K * V) == 2
    inp = _inputs(1, seed=6)
    slab = inp["state"][None]
    args = (slab, jnp.int32(0), *(inp[n][:, 0] for n in "qkvg"),
            inp["beta"][:, 0], jnp.ones((B,), bool))
    o_j, s_j = kda.kda_state_update(*args, kernel=False)
    o_k, s_k = kda.kda_state_update(*args, kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_j), atol=2e-6)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j), atol=2e-6)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_form_equals_the_recurrence_where_the_factorised_form_overflows(
        chunk):
    """37 tokens from a NON-ZERO state in chunks of 8 (a ragged last chunk),
    16 and one chunk of 64 (padded), rows of 37, 20 and 0 tokens. The
    log-decays reach -16 a token, so a chunk's cumulated log-decay passes
    float32's -88 (``exp(88.8)`` is infinite): the factorised form ``(k ⊙
    e^G)(k ⊙ e^-G)ᵀ`` would be inf x 0 there, the difference form is
    finite and equal to the recurrence to float32's rounding."""
    T, lens = 37, [37, 20, 0]
    inp = _inputs(T, seed=7)
    cum = np.cumsum(np.asarray(inp["g"]).reshape(B, T, H, K)[:, :min(chunk, T)],
                    axis=1)
    assert cum.min() < -88.8
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-cum.astype(np.float32))).all()
    o, s_out = kda.kda_chunked(*(inp[n] for n in "qkvg"), inp["beta"],
                               inp["state"], jnp.asarray(lens), chunk=chunk)
    assert np.isfinite(np.asarray(o)).all()
    for r, n in enumerate(lens):
        if not n:
            assert np.array_equal(np.asarray(s_out[r]),
                                  np.asarray(inp["state"][r]))
            continue
        want_o, want_s = _scan(inp, n)
        np.testing.assert_allclose(np.asarray(o[r, :n]),
                                   np.asarray(want_o[r]), atol=5e-6)
        np.testing.assert_allclose(np.asarray(s_out[r]),
                                   np.asarray(want_s[r]), atol=5e-6)


def test_chunked_form_with_decays_near_one_and_correlated_keys():
    """The other end: decays of 0.999 a token and keys that repeat, where the
    triangular system is far from the identity (β k·k up to 2 on every
    entry below the diagonal)."""
    T = 48
    inp = _inputs(T, seed=8, strongest=2e-3)
    inp["k"] = jnp.broadcast_to(inp["k"][:, :1], inp["k"].shape) * 0.9 \
        + 0.1 * inp["k"]
    inp["k"] = inp["k"] / jnp.linalg.norm(inp["k"], axis=-1, keepdims=True)
    o, s_out = kda.kda_chunked(*(inp[n] for n in "qkvg"), inp["beta"],
                               inp["state"], jnp.full((B,), T), chunk=16)
    want_o, want_s = _scan(inp)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(want_s),
                               atol=2e-5)


def test_a_share_of_many_experts_through_the_compacted_list():
    """``moe_experts`` for a share of 4 of 64 experts top-8 over 24 rows: 192
    assignments, a capacity of 128 rows (one ``ROW_TILE``: four times the
    uniform 12), the compact branch taken, against a dense sum over the held
    experts in float32."""
    import dataclasses

    cfg = dataclasses.replace(
        get_config("tiny-solar-open2"), num_experts=64, experts_per_token=8,
        experts_held=4, expert_offset=8)
    N, Hd, I = 24, cfg.hidden_size, cfg.moe_intermediate_size
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(k[0], (N, Hd), jnp.float32)
    router = jax.random.normal(k[1], (Hd, 64), jnp.float32) * Hd ** -0.5
    moe = {n: jax.random.normal(kk, (1, 4, *shape), jnp.float32)
           * shape[0] ** -0.5
           for n, kk, shape in (("moe_gate", k[2], (Hd, I)),
                                ("moe_up", k[3], (Hd, I)),
                                ("moe_down", k[4], (I, Hd)))}
    top_idx, gates = llama.moe_route(x, router, 8, sigmoid=True,
                                     bias=jnp.zeros((64,)), scale=1.0)
    assert llama.moe_capacity(N * 8, cfg) == 128 < N * 8
    held = int(((top_idx >= 8) & (top_idx < 12)).sum())
    assert 0 < held <= 128
    np.testing.assert_allclose(np.asarray(gates.sum(1)), 1.0, atol=1e-6)
    got = llama.moe_experts(x, top_idx, gates, moe, cfg, 0)
    want = np.zeros((N, Hd), np.float32)
    for e in range(4):
        g = np.where(np.asarray(top_idx) == 8 + e, np.asarray(gates), 0).sum(1)
        y = (jax.nn.silu(x @ moe["moe_gate"][0, e]) * (x @ moe["moe_up"][0, e])
             ) @ moe["moe_down"][0, e]
        want += g[:, None] * np.asarray(y)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-2, rtol=2e-2)


def test_both_paged_kernels_at_eight_queries_a_kv_head():
    """GQA 64/8 at tiny size (16 query heads on 2 kv heads): both kernels,
    without rotary, against attention by the formula over each row's own
    pages."""
    rows, Hq, Hkv, D, page, pmax = 2, 16, 2, 16, 8, 6
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    pools = [jax.random.normal(kk, (1, rows * pmax + 1, page, Hkv * D))
             for kk in k[:2]]
    table = jnp.asarray(1 + np.arange(rows * pmax).reshape(rows, pmax),
                        jnp.int32)

    def keys(pool, r):        # a row's pages as [tokens, Hq, D]
        flat = np.asarray(pool[0][np.asarray(table[r])]).reshape(
            pmax * page, Hkv, D)
        return np.repeat(flat, Hq // Hkv, axis=1)

    def formula(q, r, upto):  # q [Hq, D] attends tokens [:upto]
        kk, vv = keys(pools[0], r)[:upto], keys(pools[1], r)[:upto]
        s = np.einsum("hd,thd->ht", q, kk) * D ** -0.5
        p = np.exp(s - s.max(axis=1, keepdims=True))
        return np.einsum("ht,thd->hd", p / p.sum(axis=1, keepdims=True), vv)

    lens = jnp.asarray([37, 9], jnp.int32)
    q = jax.random.normal(k[2], (rows, Hq, D))
    got = paged_decode_attention(q, *pools, table, lens, interpret=True)
    for r, n in enumerate([37, 9]):
        np.testing.assert_allclose(np.asarray(got[r]),
                                   formula(np.asarray(q[r]), r, n),
                                   atol=2e-5, rtol=2e-5)
    span = jax.random.normal(k[3], (rows, 16, Hq, D))
    hist, q_lens = jnp.asarray([21, 0], jnp.int32), jnp.asarray([11, 16],
                                                                jnp.int32)
    got = ragged_paged_attention(span, *pools, table, hist, q_lens,
                                 interpret=True)
    for r, (h0, n) in enumerate([(21, 11), (0, 16)]):
        for t in range(n):
            np.testing.assert_allclose(
                np.asarray(got[r, t]),
                formula(np.asarray(span[r, t]), r, h0 + t + 1),
                atol=2e-5, rtol=2e-5)
