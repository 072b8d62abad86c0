"""The Pallas kernels granite_hybrid runs, in interpret mode at its shapes'
RATIOS (tiny sizes): the state kernel and the chunked form at ONE group and
many more than 8 heads (a row is several head blocks that share one B and
C; a block is as many heads as 1 MB of f32 state holds); the grouped matmul at 72 experts top-10; both paged attention
kernels with the softmax scale handed to them (1/D, not ``D^-1/2``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config, llama
from cyberfabric_core_tpu.models.llama import _moe_mlp, _moe_mlp_dense
from cyberfabric_core_tpu.ops import ssd
from cyberfabric_core_tpu.ops.paged_attention import (
    paged_decode_attention, ragged_paged_attention)

B, H, P, N, G = 3, 16, 8, 16, 1


def _inputs(T, seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"x": jax.random.normal(k[0], (B, T, H, P), jnp.bfloat16),
            "dt": jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 1.0),
            "a": -jnp.exp(jax.random.normal(k[2], (H,))),
            "b": jax.random.normal(k[3], (B, T, G, N), jnp.bfloat16),
            "c": jax.random.normal(k[4], (B, T, G, N), jnp.bfloat16),
            "state": jax.random.normal(k[5], (B, H, P, N), jnp.float32)}


#: ``_head_block(heads, groups, f32 bytes of a head's state)`` by shape: 1 MB
#: of state a program, a divisor of one group's heads, or WHOLE groups where
#: several fit (PR 46)
HEAD_BLOCKS = {
    "falcon-h1: 8 of a group's 16 heads of [128, 256]":
        ((32, 2, 4 * 128 * 256), 8),
    "granite: 32 of one group's 128 heads of [64, 128]":
        ((128, 1, 4 * 64 * 128), 32),
    "nemotron: two whole groups of 16 heads of [64, 128]":
        ((128, 8, 4 * 64 * 128), 32),
    "solar-open2 (ops/kda.py): 16 of 64 heads of [128, 128]":
        ((64, 1, 4 * 128 * 128), 16),
    "tiny heads: every group, the whole row": ((4, 2, 4 * 16 * 32), 4),
    "a divisor of the group's 12": ((12, 1, 4 * 64 * 1024), 4),
    "a head over the budget: one": ((6, 1, 4 * 1024 * 1024), 1),
    "three groups fit, of 8: a divisor of the groups, two":
        ((64, 8, 4 * 64 * 170), 16),
    "a group and a half fit: one group": ((32, 2, 4 * 64 * 170), 16),
}


@pytest.mark.parametrize("case", list(HEAD_BLOCKS))
def test_the_head_block_follows_from_the_shapes(case):
    """1 MB of f32 state a program: falcon-h1 keeps its 8 heads of [128,
    256] (4 programs a row), granite's [64, 128] take 32 (4 programs a row,
    not 16), nemotron's groups of 16 go two to a program (granite's block);
    a block is whole groups or divides one, and never leaves a remainder."""
    (heads, groups, head_bytes), want = HEAD_BLOCKS[case]
    got = ssd._head_block(heads, groups, head_bytes)
    assert got == want
    per_group = heads // groups
    assert heads % got == 0 and (per_group % got == 0 or got % per_group == 0)


def test_state_kernel_at_one_group_and_two_head_blocks_a_row(monkeypatch):
    # two blocks of 8 a row at these tiny heads: the served ratio of blocks
    monkeypatch.setattr(ssd, "_STATE_BLOCK_BYTES", 8 * 4 * P * N)
    assert ssd._head_block(H, G, 4 * P * N) == 8
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
    # the masked row, the other layer and the row beyond the batch: bitwise
    assert np.array_equal(np.asarray(s_k[1, 1]), np.asarray(slab[1, 1]))
    assert np.array_equal(np.asarray(s_k[0]), np.asarray(slab[0]))
    assert np.array_equal(np.asarray(s_k[1, 3]), np.asarray(slab[1, 3]))
    assert not np.array_equal(np.asarray(s_k[1, 0]), np.asarray(slab[1, 0]))


def test_chunked_form_at_one_group_equals_the_step_token_by_token():
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
    assert np.array_equal(np.asarray(s_out[2]), np.asarray(inp["state"][2]))


@pytest.mark.parametrize("tokens", [64])
def test_dropless_72_experts_top10(tokens):
    """A decode step's 64 rows (640 assignments, 8.9 an expert; a mixed
    step's more is ``test_moe.py``'s 300 at 128 experts): int8, every token
    keeps all 10 contributions."""
    from cyberfabric_core_tpu.runtime.quant import quantize_weight

    cfg = dataclasses.replace(get_config("tiny-moe"), num_experts=72,
                              experts_per_token=10, intermediate_size=16,
                              num_layers=1)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    for name in llama.MOE_LEAVES:
        lp[name] = quantize_weight(lp[name])
    x = jax.random.normal(jax.random.PRNGKey(tokens),
                          (1, tokens, cfg.hidden_size))
    np.testing.assert_allclose(np.asarray(_moe_mlp(x, lp, cfg)),
                               np.asarray(_moe_mlp_dense(x, lp, cfg)),
                               rtol=2e-4, atol=2e-4)


def test_both_paged_kernels_take_the_scale_they_are_handed():
    """``scale=1/D`` equals the kernel as it was on queries pre-multiplied by
    ``D^-1/2`` (float32, so the pre-multiply rounds nothing away); absent, the
    kernel is the kernel as it was."""
    rows, Hq, Hkv, D, page, pmax = 2, 8, 2, 16, 8, 4
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    pools = [jax.random.normal(kk, (1, rows * pmax + 1, page, Hkv * D))
             for kk in k[:2]]
    table = jnp.asarray(1 + np.arange(rows * pmax).reshape(rows, pmax),
                        jnp.int32)
    lens = jnp.asarray([27, 9], jnp.int32)
    q = jax.random.normal(k[2], (rows, Hq, D))
    work = table, lens
    given = paged_decode_attention(q, *pools, *work, interpret=True,
                                   scale=1.0 / D)
    as_was = paged_decode_attention(q * D ** -0.5, *pools, *work,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(given), np.asarray(as_was),
                               atol=1e-5, rtol=1e-5)
    assert not np.allclose(np.asarray(given), np.asarray(
        paged_decode_attention(q, *pools, *work, interpret=True)), atol=1e-3)
    span = jax.random.normal(k[3], (rows, 8, Hq, D))
    hist, q_lens = jnp.asarray([16, 0], jnp.int32), jnp.asarray([8, 5],
                                                                jnp.int32)
    given = ragged_paged_attention(span, *pools, table, hist, q_lens,
                                   interpret=True, scale=1.0 / D)
    as_was = ragged_paged_attention(span * D ** -0.5, *pools, table, hist,
                                    q_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(given), np.asarray(as_was),
                               atol=1e-5, rtol=1e-5)
