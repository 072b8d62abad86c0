"""Seeded int8 weights of the Granite-4.0-H stack for the correctness check,
made by the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/granite_hybrid.py``): three stacks. ``mamba`` over the mamba layers
(``ssm_in``, ``ssm_out`` as ``{"q": int8 [Lm, in, out], "s": f32 [Lm, out]}``;
the conv's taps and bias, ``A_log``, ``D``, ``dt_bias`` and the gated norm's
weight float32), ``attention`` over the attention layers (``wq``, ``wk``,
``wv``, ``wo``), and ``layers`` over ALL layers: the two norms near 1, the
float32 ``router`` ``[L, H, E]``, the shared MLP (``shared_gate``,
``shared_up``, ``shared_down``) and the experts ``moe_gate`` / ``moe_up``
``[L, E, H, I]``, ``moe_down`` ``[L, E, I, H]``. The embedding is ``{"qe",
"se"}`` and is the head too (tied). ``weights.to_int4_grid`` walks every
``{"q", "s"}`` node of it.

Every matrix is drawn at ``fan_in^-1/2``, the router too (a token's logits
over the experts are then of unit spread); the mixer's small leaves as
``falcon_h1_weights.py`` draws them (decays neither 0 nor 1).

**The published multipliers go with trained weights.** Applied to matrices
drawn at ``fan_in^-1/2`` they would leave every branch at a fifth of the
residual (0.22), the embedding 12 times it, and the attention scores at 0.09
of a unit (1/128 where ``head_dim^-1/2`` is 1/11.3): a comparison of logits
would then see little but the embedding, and a wrong page or a wrong state
would pass. So each matrix that a multiplier follows is drawn larger by that
multiplier's inverse (its f32 scales are: int8 rounding is unchanged): the
embedding's rows by 1/12, the four output projections into the residual
stream (``ssm_out``, ``wo``, ``moe_down``, ``shared_down``) by 1/0.22, and
``wq`` by ``1 / (attention_multiplier · head_dim^1/2)``, so that scores are of
order 1. The multipliers themselves stay as published, in program and
reference alike. ``logits_scaling`` divides both sides' logits and moves no
ratio of them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import weights as base

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


def _stack_leaf(key, layers: int, lead: tuple, fan_in: int, fan_out: int,
                gain: float = 1.0) -> dict:
    """``[layers, *lead, fan_in, fan_out]``, a layer at a time (the f32 draw
    of one layer's 72 experts is 0.9 GB a matrix); ``gain`` multiplies the
    scales."""
    def one_layer(k):
        w = jax.random.normal(k, (*lead, fan_in, fan_out),
                              jnp.float32) * fan_in ** -0.5
        q, s = base._quantize(w, axis=len(lead))
        return q, s * gain

    q, s = jax.lax.map(one_layer, jax.random.split(key, layers))
    return {"q": q, "s": s}


def _norm(key, *shape):   # near 1, so a dropped norm weight shows
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "shared", "vocab", "layers", "mamba_layers",
    "attn_layers", "dq", "dkv", "d_inner", "ssm_heads", "conv_dim", "d_conv",
    "experts", "embedding_m", "residual_m", "score_m"))
def _make(key, *, hidden, inter, shared, vocab, layers, mamba_layers,
          attn_layers, dq, dkv, d_inner, ssm_heads, conv_dim, d_conv, experts,
          embedding_m, residual_m, score_m):
    k = iter(jax.random.split(key, 32))
    out = 1.0 / residual_m      # the projections into the residual stream
    step = jnp.exp(jax.random.uniform(
        next(k), (mamba_layers, ssm_heads), jnp.float32, math.log(1e-3),
        math.log(1e-1)))
    mamba = {
        "ssm_in": _stack_leaf(next(k), mamba_layers, (), hidden,
                              d_inner + conv_dim + ssm_heads),
        "ssm_out": _stack_leaf(next(k), mamba_layers, (), d_inner, hidden,
                               out),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(
            next(k), (mamba_layers, ssm_heads), jnp.float32, 1.0, 16.0)),
        "D": 1.0 + 0.1 * jax.random.normal(
            next(k), (mamba_layers, ssm_heads), jnp.float32),
        "conv_w": jax.random.uniform(
            next(k), (mamba_layers, d_conv, conv_dim), jnp.float32,
            -d_conv ** -0.5, d_conv ** -0.5),
        "conv_b": 0.1 * jax.random.normal(
            next(k), (mamba_layers, conv_dim), jnp.float32),
        "ssm_norm": 1.0 + 0.1 * jax.random.normal(
            next(k), (mamba_layers, d_inner), jnp.float32),
    }
    attention = {
        "wq": _stack_leaf(next(k), attn_layers, (), hidden, dq, 1.0 / score_m),
        "wk": _stack_leaf(next(k), attn_layers, (), hidden, dkv),
        "wv": _stack_leaf(next(k), attn_layers, (), hidden, dkv),
        "wo": _stack_leaf(next(k), attn_layers, (), dq, hidden, out),
    }
    every = {
        "attn_norm": _norm(next(k), layers, hidden),
        "mlp_norm": _norm(next(k), layers, hidden),
        "router": jax.random.normal(next(k), (layers, hidden, experts),
                                    jnp.float32) * hidden ** -0.5,
        "shared_gate": _stack_leaf(next(k), layers, (), hidden, shared),
        "shared_up": _stack_leaf(next(k), layers, (), hidden, shared),
        "shared_down": _stack_leaf(next(k), layers, (), shared, hidden, out),
        "moe_gate": _stack_leaf(next(k), layers, (experts,), hidden, inter),
        "moe_up": _stack_leaf(next(k), layers, (experts,), hidden, inter),
        "moe_down": _stack_leaf(next(k), layers, (experts,), inter, hidden,
                                out),
    }
    embed = jax.random.normal(next(k), (vocab, hidden), jnp.float32)
    qe, se = base._quantize(embed, axis=1)
    return {"embed": {"qe": qe, "se": se / embedding_m},
            "final_norm": _norm(next(k), hidden),
            "mamba": mamba, "attention": attention, "layers": every}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole tree on the device from the seed, the first ``layers``
    layers of ``layer_types``. ``cfg`` is the configuration file (the
    published keys)."""
    kinds = list(cfg["layer_types"][:layers])
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    head_dim = hidden // heads
    d_inner = cfg["mamba_expand"] * hidden
    conv_dim = d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return _make(
        seed_key(seed), hidden=hidden, inter=cfg["intermediate_size"],
        shared=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        layers=layers, mamba_layers=kinds.count("mamba"),
        attn_layers=kinds.count("attention"), dq=heads * head_dim,
        dkv=cfg["num_key_value_heads"] * head_dim, d_inner=d_inner,
        ssm_heads=cfg["mamba_n_heads"], conv_dim=conv_dim,
        d_conv=cfg["mamba_d_conv"], experts=cfg["num_local_experts"],
        embedding_m=float(cfg["embedding_multiplier"]),
        residual_m=float(cfg["residual_multiplier"]),
        score_m=float(cfg["attention_multiplier"]) * head_dim ** 0.5)
