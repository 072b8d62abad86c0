"""The Pallas kernels nemotron_h runs, in interpret mode at its shapes' RATIOS
(tiny sizes): the state kernel and the chunked form at SEVERAL groups of 16
heads (a program takes whole groups, two at the served size: PR 46; one
where the budget holds one);
the grouped matmul in its two-matrix form on rows of a latent width, with
many more held experts than rows; both paged attention kernels at 16 queries
a kv head on a page row of 2 kv heads."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config, llama
from cyberfabric_core_tpu.ops import ssd
from cyberfabric_core_tpu.ops.grouped_matmul import (BLOCK_BYTES, ROW_TILE,
                                                     _col_tile, group_items,
                                                     row_tile)
from cyberfabric_core_tpu.ops.paged_attention import (
    decode_trip_pages, paged_decode_attention, ragged_paged_attention)
from cyberfabric_core_tpu.runtime.quant import quantize_weight

B, H, P, N, G = 3, 32, 8, 16, 2          # 16 heads a group, as served


def _inputs(T, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"x": jax.random.normal(k[0], (B, T, H, P), jnp.bfloat16),
            "dt": jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 1.0),
            "a": -jnp.exp(jax.random.normal(k[2], (H,))),
            "b": jax.random.normal(k[3], (B, T, G, N), jnp.bfloat16),
            "c": jax.random.normal(k[4], (B, T, G, N), jnp.bfloat16),
            "state": jax.random.normal(k[5], (B, H, P, N), jnp.float32)}


def test_the_tiles_follow_from_the_shapes_and_the_others_keep_theirs():
    """8 groups of 16 heads of [64, 128] give TWO whole groups a program (32
    heads, 1 MB, granite's block: PR 46; a group alone was 512 KB and 512
    programs a call; falcon's 8 of a group stay); an expert's 1024 x 2688
    and 2688 x 1024 int8 matrices are one
    block each, no column tiles (kimi's 7168 x 2048 keeps its 512, sdar's and
    granite's stay whole); a decode step's 1408 sorted assignments over 128
    held experts are 22 row tiles of 64 (11 rows an expert: ``row_tile``, PR
    43) + 127 = 149 work items; a page row of 2 kv
    heads takes 8 pages a program (mistral's 8 kv heads 4)."""
    assert ssd._head_block(128, 8, 4 * 64 * 128) == 32          # nemotron
    assert ssd._head_block(128, 1, 4 * 64 * 128) == 32          # granite
    assert ssd._head_block(32, 2, 4 * 128 * 256) == 8           # falcon-h1
    assert ssd._head_block(64, 1, 4 * 128 * 128) == 16          # solar-open2
    assert _col_tile(1024, 2688, 1) == 2688 and 1024 * 2688 <= BLOCK_BYTES
    assert _col_tile(2688, 1024, 1) == 1024
    assert _col_tile(7168, 2048, 1) == 512                      # kimi
    assert _col_tile(4096, 768, 1) == 768                       # granite
    assert _col_tile(2048, 768, 1) == 768                       # sdar
    sizes = jnp.zeros((128,), jnp.int32).at[:100].set(3)
    assert row_tile(1408, 128) == 64
    tile, *_ = group_items(sizes, 1408, row_tile(1408, 128))
    assert tile.shape == (22 + 127,)
    assert decode_trip_pages(64, 2 * 128, 2, 64, None) == 16    # nemotron
    assert decode_trip_pages(64, 8 * 128, 2, 32, 4096) == 16    # mistral


#: the grouped matmul calls the benchmark's four MoE cells run (rows of the
#: sorted assignments, experts held) and the row tile each gets
CELL_CALLS = {
    "sdar decode forward: 64 tokens top-8 over 128": (512, 128, 64),
    "granite decode step: 64 rows top-10 over 72": (640, 72, 64),
    "kimi decode step: the compacted 128 rows over 12 held": (128, 12, 64),
    "nemotron decode step: 64 rows top-22 over 128 held": (1408, 128, 64),
    "granite mixed step: 576 tokens top-10 over 72": (5760, 72, 128),
    "nemotron mixed step: 576 tokens top-22 over 128 held": (12672, 128, 128),
    "kimi mixed step: the compacted 640 rows over 12 held": (640, 12, 64),
}


@pytest.mark.parametrize("call", list(CELL_CALLS))
def test_the_row_tile_follows_from_the_calls_shapes_alone(call):
    """``row_tile(M, E)``: 64 rows where the mean group has at most 64
    (every decode step of the four cells: 4-11 rows an expert; kimi's
    compacted mixed step: 53), 128 above (a 512-token mixed step of granite
    and nemotron: 80 and 99), as the kernel-alone probe read them (PERF.md
    section 5); the work items' count follows from the tile."""
    m, groups, tile = CELL_CALLS[call]
    assert row_tile(m, groups) == tile
    items, *_ = group_items(jnp.zeros((groups,), jnp.int32), m, tile)
    assert items.shape == (m // tile + groups - 1,)


def test_the_row_tile_of_calls_no_cell_runs():
    """A call of fewer rows than the tile is one tile of its rows (whole
    sublane tiles of 16); the boundary is the mean group's 64 rows; the
    tile never passes ``ROW_TILE``, which stays the unit of
    ``moe_capacity``."""
    assert [row_tile(m, 8) for m in (1, 16, 17, 40, 64, 65)] == \
        [16, 16, 32, 48, 64, 64]
    assert row_tile(64 * 72, 72) == 64 and row_tile(64 * 72 + 1, 72) == 128
    assert row_tile(1 << 20, 2) == ROW_TILE == 128
    assert row_tile(100, 1) == 112            # one group: one tile


@pytest.mark.parametrize("groups_a_program", [1, 2])
def test_state_kernel_at_several_groups_of_sixteen_heads(monkeypatch,
                                                         groups_a_program):
    """A program of ONE whole group (the budget holds 16 heads) and of TWO
    (the served ratio: a head reads row ``j // 16`` of its block's B and C)."""
    monkeypatch.setattr(ssd, "_STATE_BLOCK_BYTES",
                        groups_a_program * 16 * 4 * P * N)
    assert ssd._head_block(H, G, 4 * P * N) == 16 * groups_a_program
    inp = _inputs(1, seed=5)
    slab = jnp.stack([inp["state"] * 0.5, inp["state"]])
    slab = jnp.concatenate([slab, slab[:, :1] + 1.0], axis=1)   # a 4th row
    mask = jnp.asarray([True, False, True])
    args = (slab, jnp.int32(1), inp["x"][:, 0], inp["dt"][:, 0], inp["a"],
            inp["b"][:, 0], inp["c"][:, 0], mask)
    y_j, s_j = ssd.ssm_state_update(*args, kernel=False)
    y_k, s_k = ssd.ssm_state_update(*args, kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j), atol=1e-6)
    # a head reads ITS group's B and C: swapping the groups moves the result
    swapped = ssd.ssm_state_update(
        *args[:5], inp["b"][:, 0, ::-1], inp["c"][:, 0, ::-1], mask,
        kernel=True, interpret=True)[0]
    assert not np.allclose(np.asarray(swapped), np.asarray(y_k), atol=1e-3)
    # the masked row, the other layer and the row beyond the batch: bitwise
    assert np.array_equal(np.asarray(s_k[1, 1]), np.asarray(slab[1, 1]))
    assert np.array_equal(np.asarray(s_k[0]), np.asarray(slab[0]))
    assert np.array_equal(np.asarray(s_k[1, 3]), np.asarray(slab[1, 3]))


def test_chunked_form_at_several_groups_equals_the_step_token_by_token():
    T, q_lens = 20, jnp.asarray([20, 13, 0])
    inp = _inputs(T, seed=7)
    d = jnp.linspace(0.5, 1.5, H)
    y, s_out = ssd.ssd_chunked(inp["x"], inp["dt"], inp["a"], inp["b"],
                               inp["c"], d, inp["state"], q_lens, chunk=8)
    slab, want = inp["state"][None], np.zeros((B, T, H, P), np.float32)
    for t in range(T):
        y_t, slab = ssd.ssm_state_update(
            slab, jnp.int32(0), inp["x"][:, t], inp["dt"][:, t], inp["a"],
            inp["b"][:, t], inp["c"][:, t], t < q_lens, kernel=False)
        want[:, t] = np.asarray(
            y_t + d[:, None] * inp["x"][:, t].astype(jnp.float32))
    for r, n in enumerate([20, 13, 0]):
        np.testing.assert_allclose(np.asarray(y[r, :n]), want[r, :n],
                                   atol=3e-2, rtol=3e-2)   # bf16 operands
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(slab[0]),
                               atol=3e-2, rtol=3e-2)


def test_two_matrix_experts_on_latent_rows_with_more_experts_than_rows():
    """``moe_experts`` in the form a tree without ``moe_gate`` gives it:
    ``relu(u W1)² W2`` on rows of a latent width (32 of a hidden 64), int8,
    32 held experts of 128 routed for 8 rows top-6 (most held experts get no
    row, a few one), against every held expert computed for every row."""
    cfg = dataclasses.replace(
        get_config("tiny-nemotron-h"), num_experts=128, experts_per_token=6,
        experts_held=32, expert_offset=32)
    W, I, El = cfg.moe_latent_size, 84, 32      # 2.625 x the latent, as served
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    moe = {"moe_up": quantize_weight(
               jax.random.normal(k[0], (2, El, W, I)) * W ** -0.5),
           "moe_down": quantize_weight(
               jax.random.normal(k[1], (2, El, I, W)) * I ** -0.5)}
    u = jax.random.normal(k[2], (8, W), jnp.float32)
    top_idx = jnp.stack([jax.random.permutation(kk, 128)[:6]
                         for kk in jax.random.split(k[3], 8)]).astype(jnp.int32)
    top_idx = top_idx.at[0, 0].set(40).at[1, 0].set(40)   # one shared by two
    gates = jax.random.uniform(k[4], (8, 6), jnp.float32, 0.1, 1.0)
    got = llama.moe_experts(u, top_idx, gates, moe, cfg, jnp.int32(1))
    assert got.shape == (8, W)
    want = np.zeros((8, W), np.float32)
    for e in range(El):
        up = np.asarray(moe["moe_up"]["q"][1, e], np.float32) \
            * np.asarray(moe["moe_up"]["s"][1, e])
        down = np.asarray(moe["moe_down"]["q"][1, e], np.float32) \
            * np.asarray(moe["moe_down"]["s"][1, e])
        g = np.where(np.asarray(top_idx) == 32 + e, np.asarray(gates),
                     0.0).sum(axis=1, keepdims=True)
        want += g * (np.square(np.maximum(np.asarray(u) @ up, 0.0)) @ down)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_both_paged_kernels_at_sixteen_queries_a_kv_head():
    """32 query heads on 2 kv heads: a page row of 2 x D numbers. Both
    kernels against attention by the formula over each row's own pages."""
    rows, Hq, Hkv, D, page, pmax = 2, 32, 2, 16, 8, 4
    k = jax.random.split(jax.random.PRNGKey(0), 4)
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

    lens = jnp.asarray([27, 9], jnp.int32)
    q = jax.random.normal(k[2], (rows, Hq, D))
    for trip in (1, 2):
        got = paged_decode_attention(q, *pools, table, lens, interpret=True,
                                     trip=trip)
        for r, n in enumerate([27, 9]):
            np.testing.assert_allclose(np.asarray(got[r]),
                                       formula(np.asarray(q[r]), r, n),
                                       atol=2e-5, rtol=2e-5)
    span = jax.random.normal(k[3], (rows, 8, Hq, D))
    hist, q_lens = jnp.asarray([16, 0], jnp.int32), jnp.asarray([8, 5],
                                                                jnp.int32)
    got = ragged_paged_attention(span, *pools, table, hist, q_lens,
                                 interpret=True)
    for r, (h0, n) in enumerate([(16, 8), (0, 5)]):
        for t in range(n):
            np.testing.assert_allclose(
                np.asarray(got[r, t]),
                formula(np.asarray(span[r, t]), r, h0 + t + 1),
                atol=2e-5, rtol=2e-5)
