"""Grouped (routed) MoE vs the dense oracle: the dropless expert layer (sorted
assignments through ``ops/grouped_matmul.py``) computes what the dense
formulation computes, at any batch and however the tokens fall."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import llama
from cyberfabric_core_tpu.models.configs import get_config
from cyberfabric_core_tpu.models.llama import _moe_mlp, _moe_mlp_dense


def _setup(B=2, T=16, **changes):
    cfg = dataclasses.replace(get_config("tiny-moe"), **changes)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer 0 slice
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.hidden_size),
                          jnp.float32)
    return cfg, lp, x


def test_grouped_matches_dense():
    """No token is dropped: grouped == dense."""
    cfg, lp, x = _setup()
    dense = np.asarray(_moe_mlp_dense(x, lp, cfg))
    grouped = np.asarray(_moe_mlp(x, lp, cfg))
    np.testing.assert_allclose(grouped, dense, rtol=2e-5, atol=2e-5)


def test_grouped_decode_shape():
    cfg, lp, _ = _setup()
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, cfg.hidden_size),
                          jnp.float32)
    out = _moe_mlp(x, lp, cfg)
    assert out.shape == (4, 1, cfg.hidden_size)
    dense = np.asarray(_moe_mlp_dense(x, lp, cfg))
    np.testing.assert_allclose(np.asarray(out), dense, rtol=2e-5, atol=2e-5)


def test_moe_model_forward_still_matches_paged():
    """End-to-end: tiny-moe forward (which now routes) stays consistent
    between the dense-cache and paged-decode paths (checked in
    tests/test_paged_decode.py too — here we pin prefill+decode greedy)."""
    cfg = get_config("tiny-moe")
    from cyberfabric_core_tpu.ops.rope import rope_frequencies
    rope = rope_frequencies(cfg.head_dim, cfg.max_position, cfg.rope_theta)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    cache = llama.init_cache(cfg, 1, 32, jnp.float32)
    positions = jnp.arange(8)[None, :].astype(jnp.int32)
    h, cache = llama.forward(params, cfg, ids, positions, cache,
                             jnp.zeros((1,), jnp.int32), rope)
    assert np.isfinite(np.asarray(h)).all()


def _wide(quant: bool, dtype=jnp.float32):
    """128 experts top-8 at tiny widths: one layer's leaves, stacked."""
    from cyberfabric_core_tpu.runtime.quant import quantize_weight

    cfg = dataclasses.replace(get_config("tiny-moe"), num_experts=128,
                              experts_per_token=8, intermediate_size=32,
                              num_layers=1)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), dtype)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    if quant:
        for name in llama.MOE_LEAVES:
            lp[name] = quantize_weight(lp[name])
    return cfg, lp


@pytest.mark.parametrize("quant", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("tokens", [1, 64, 300])
def test_dropless_128_experts_top8(tokens, quant):
    """The served shape class: 128 experts, 8 a token, a decode step's 64
    tokens and a mixed step's more than 256 (where the capacity rule this
    layer replaced began to drop). Every token keeps all 8 contributions."""
    cfg, lp = _wide(quant)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens,
                                                       cfg.hidden_size))
    np.testing.assert_allclose(np.asarray(_moe_mlp(x, lp, cfg)),
                               np.asarray(_moe_mlp_dense(x, lp, cfg)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("quant", [False, True], ids=["float32", "int8"])
def test_dropless_every_token_on_one_expert(quant):
    """300 identical tokens: every one of the 2400 assignments falls on the
    same 8 experts, 300 rows each, more than any capacity rule gave one."""
    cfg, lp = _wide(quant)
    row = jax.random.normal(jax.random.PRNGKey(9), (1, 1, cfg.hidden_size))
    x = jnp.broadcast_to(row, (1, 300, cfg.hidden_size))
    out = np.asarray(_moe_mlp(x, lp, cfg))
    np.testing.assert_allclose(out, np.asarray(_moe_mlp_dense(x, lp, cfg)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(out[0, 0], out[0, -1], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("sizes", [[40], [0, 0, 7, 0, 300, 0, 1, 0],
                                   [5] * 128, [0] * 127 + [130]],
                         ids=["one-group", "ragged", "even", "last"])
def test_grouped_matmul_against_ragged_dot(sizes):
    """The kernel alone, a layer picked out of a stack, int8 with scales,
    against ``jax.lax.ragged_dot`` on that layer."""
    from cyberfabric_core_tpu.ops.grouped_matmul import grouped_matmul

    E, M, K, N = len(sizes), sum(sizes), 64, 32
    ks = jax.random.split(jax.random.PRNGKey(E), 3)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    w = jax.random.randint(ks[1], (3, E, K, N), -127, 127, jnp.int8)
    s = jax.random.uniform(ks[2], (3, E, N), jnp.float32, 0.5, 1.5)
    got = grouped_matmul(x, w, s, jnp.asarray(sizes, jnp.int32), 2,
                         interpret=True)
    want = jax.lax.ragged_dot(x, w[2].astype(jnp.float32),
                              jnp.asarray(sizes, jnp.int32),
                              precision="highest")
    want = want * np.asarray(s[2])[np.repeat(np.arange(E), sizes)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-3)


#: sizes of the groups and the rows of the call (more than the groups' sum
#: where a chip holds a share: the last rows belong to no group)
TILE_CASES = {
    "spans-three-tiles": ([10, 300, 5], 315),
    "empty-experts": ([0, 0, 7, 0, 40, 0, 1, 0], 48),
    "one-expert": ([0] * 7 + [200], 200),
    "held-share": ([3, 0, 20, 9], 100),
    "ragged-rows": ([30, 0, 47], 77),
}
TILES = (16, 32, 64, 128)


def _tile_case(case, quant):
    sizes, M = TILE_CASES[case]
    E, K, N = len(sizes), 64, 128
    ks = jax.random.split(jax.random.PRNGKey(M), 3)
    x = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    if quant:
        w = jax.random.randint(ks[1], (2, E, K, N), -127, 127, jnp.int8)
        s = jax.random.uniform(ks[2], (2, E, N), jnp.float32, 0.5, 1.5)
    else:
        w = jax.random.normal(ks[1], (2, E, K, N), jnp.bfloat16)
        s = None
    return x, w, s, jnp.asarray(sizes, jnp.int32)


_AT_TILE_16 = {}


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bfloat16"])
@pytest.mark.parametrize("case", list(TILE_CASES))
@pytest.mark.parametrize("tile", TILES)
def test_a_rows_result_does_not_depend_on_the_row_tile(tile, case, quant):
    """The kernel (interpret mode) at every row tile the rule can pick,
    against ``jax.lax.ragged_dot`` on the layer: an expert whose rows span
    three tiles of 128, empty experts, every row on one expert, a held share
    whose last rows belong to no group, rows that are no multiple of the
    tile. And the tile is no part of the mathematics: a row's result at this
    tile is, bit for bit, its result at 16."""
    from cyberfabric_core_tpu.ops.grouped_matmul import grouped_matmul

    x, w, s, sizes = _tile_case(case, quant)
    real = int(sizes.sum())

    def at(rows):
        return np.asarray(grouped_matmul(x, w, s, sizes, 1, interpret=True,
                                         tile_rows=rows))[:real]

    got = at(tile)
    want = jax.lax.ragged_dot(x[:real].astype(jnp.float32),
                              w[1].astype(jnp.float32), sizes,
                              precision="highest")
    if quant:
        want = want * np.asarray(s[1])[np.repeat(np.arange(len(sizes)),
                                                 np.asarray(sizes))]
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-3)
    if (case, quant) not in _AT_TILE_16:
        _AT_TILE_16[case, quant] = got if tile == 16 else at(16)
    np.testing.assert_array_equal(got, _AT_TILE_16[case, quant])


@pytest.mark.parametrize("tile", TILES)
def test_work_items_cover_every_real_row_once(tile):
    """``group_items`` at each tile, over sizes drawn at random (empty
    groups, one group with every row, a share whose rows end before the
    call's): every real row lies in exactly one item's (tile, row range),
    the item count follows from shapes alone, and the padding items repeat
    the last real one."""
    from cyberfabric_core_tpu.ops.grouped_matmul import (group_items,
                                                         item_rows, row_tile)

    rng = np.random.default_rng(tile)
    E, m_pad = 24, 40 * tile            # one shape a tile: one compile
    for trial in range(12):
        sizes = rng.integers(0, 3 * tile, E) * (rng.random(E) < 0.6)
        if trial == 0:
            sizes[:] = 0
        if trial == 1:
            sizes[:] = 0
            sizes[-1] = 5 * tile + 3
        if trial == 2:
            sizes[:] = m_pad // E
        while sizes.sum() > m_pad:
            sizes[np.argmax(sizes)] = 0
        real = int(sizes.sum())
        t, e, lo, hi, n = (np.asarray(a) for a in group_items(
            jnp.asarray(sizes, jnp.int32), m_pad, tile))
        n = int(n[0])
        assert t.shape == (m_pad // tile + min(E, m_pad) - 1,)
        covered = np.zeros(m_pad, np.int64)
        starts = np.cumsum(sizes) - sizes
        for i in range(n):
            rows = np.arange(t[i] * tile, (t[i] + 1) * tile)
            mine = rows[(rows >= lo[i]) & (rows < hi[i])]
            assert mine.size, "a real item has a row"
            assert (lo[i], hi[i]) == (starts[e[i]], starts[e[i]] + sizes[e[i]])
            covered[mine] += 1
        assert (covered[:real] == 1).all() and not covered[real:].any()
        assert (np.diff(t[:n]) >= 0).all() and (np.diff(e[:n]) >= 0).all()
        if n:
            for a in (t, e, lo, hi):
                assert (a[n:] == a[n - 1]).all()
        # the counter's arithmetic is the items': real items x the tile
        # the rule picks for these shapes
        tm = row_tile(m_pad, E)
        *_, n_rule = group_items(jnp.asarray(sizes, jnp.int32),
                                 -(-m_pad // tm) * tm, tm)
        assert int(item_rows(jnp.asarray(sizes, jnp.int32), m_pad)) == \
            int(n_rule[0]) * tm
