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
