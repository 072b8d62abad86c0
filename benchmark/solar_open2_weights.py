"""Seeded int8 weights of the Solar-Open2 stack for the correctness check,
made by the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/solar_open2.py``): three stacks. ``kda`` over the KDA layers (``wq``,
``wk``, ``wv``, ``wo``, the decay's low-rank pair ``f_a`` / ``f_b``, the
output gate's ``g_a`` / ``g_b`` and ``w_beta`` as ``{"q": int8 [Lk, in, out],
"s": f32 [Lk, out]}``; the conv's taps ``conv_w`` [Lk, taps, q k v channels],
``A_log`` [Lk, heads], ``dt_bias`` [Lk, heads x d] and the head norm's weight
``o_norm`` [Lk, d] float32), ``attention`` over the GQA layers (``wq``,
``wk``, ``wv``, ``wo``, ``w_gate``), and ``layers`` over ALL layers: the two
norms near 1, the float32 ``router`` ``[L, H, E routed]`` and its selection
bias ``[L, E routed]``, the shared expert (``shared_gate``, ``shared_up``,
``shared_down``) and the experts HELD ``moe_gate`` / ``moe_up`` ``[L, held,
H, I]``, ``moe_down`` ``[L, held, I, H]``. The embedding is ``{"qe", "se"}``
over the held rows of the vocabulary, the head (untied) ``lm_head`` ``{"q":
[H, V held], "s"}``. ``weights.to_int4_grid`` walks every ``{"q", "s"}`` node.

Every matrix is drawn at ``fan_in^-1/2``, the router too (a token's logits
over the experts are then of unit spread); the decays are drawn as
``falcon_h1_weights.py`` draws its own, so that none is 0 or 1: ``A_log =
log U(1, 16)`` a head, ``dt_bias`` a channel the inverse softplus of a step
log-uniform in [1e-3, 1e-1]; with the low-rank projection's unit spread in
front of the softplus a token's log-decay runs from about −0.001 to −40 a
channel, so a chunk's cumulated log-decay passes float32's −88 in a few
tokens on some channels and stays near 0 on others. The selection bias is
drawn at 0.1 N(0, 1) HERE, so that a dropped bias shows; the served model's
is zero (``models/solar_open2.init_params_with``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import weights as base
# a stacked int8 leaf drawn a layer at a time (the f32 draw of one layer's 40
# held experts is 0.84 GB a matrix), and a norm near 1
from .granite_hybrid_weights import _norm, _stack_leaf

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "shared", "vocab", "layers", "kda_layers",
    "attn_layers", "dq", "dkv", "kda_heads", "kda_dim", "d_conv", "experts",
    "held"))
def _make(key, *, hidden, inter, shared, vocab, layers, kda_layers,
          attn_layers, dq, dkv, kda_heads, kda_dim, d_conv, experts, held):
    k = iter(jax.random.split(key, 40))
    width = kda_heads * kda_dim
    step = jnp.exp(jax.random.uniform(
        next(k), (kda_layers, width), jnp.float32, math.log(1e-3),
        math.log(1e-1)))

    def kda_leaf(fan_in, fan_out):
        return _stack_leaf(next(k), kda_layers, (), fan_in, fan_out)

    kda = {
        "wq": kda_leaf(hidden, width), "wk": kda_leaf(hidden, width),
        "wv": kda_leaf(hidden, width), "wo": kda_leaf(width, hidden),
        "f_a": kda_leaf(hidden, kda_dim), "f_b": kda_leaf(kda_dim, width),
        "g_a": kda_leaf(hidden, kda_dim), "g_b": kda_leaf(kda_dim, width),
        "w_beta": kda_leaf(hidden, kda_heads),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(
            next(k), (kda_layers, kda_heads), jnp.float32, 1.0, 16.0)),
        "conv_w": jax.random.uniform(
            next(k), (kda_layers, d_conv, 3 * width), jnp.float32,
            -d_conv ** -0.5, d_conv ** -0.5),
        "o_norm": 1.0 + 0.1 * jax.random.normal(
            next(k), (kda_layers, kda_dim), jnp.float32),
    }
    attention = {
        "wq": _stack_leaf(next(k), attn_layers, (), hidden, dq),
        "wk": _stack_leaf(next(k), attn_layers, (), hidden, dkv),
        "wv": _stack_leaf(next(k), attn_layers, (), hidden, dkv),
        "wo": _stack_leaf(next(k), attn_layers, (), dq, hidden),
        "w_gate": _stack_leaf(next(k), attn_layers, (), hidden, dq),
    }
    every = {
        "attn_norm": _norm(next(k), layers, hidden),
        "mlp_norm": _norm(next(k), layers, hidden),
        "router": jax.random.normal(next(k), (layers, hidden, experts),
                                    jnp.float32) * hidden ** -0.5,
        "router_bias": 0.1 * jax.random.normal(
            next(k), (layers, experts), jnp.float32),
        "shared_gate": _stack_leaf(next(k), layers, (), hidden, shared),
        "shared_up": _stack_leaf(next(k), layers, (), hidden, shared),
        "shared_down": _stack_leaf(next(k), layers, (), shared, hidden),
        "moe_gate": _stack_leaf(next(k), layers, (held,), hidden, inter),
        "moe_up": _stack_leaf(next(k), layers, (held,), hidden, inter),
        "moe_down": _stack_leaf(next(k), layers, (held,), inter, hidden),
    }
    embed = jax.random.normal(next(k), (vocab, hidden), jnp.float32)
    qe, se = base._quantize(embed, axis=1)
    head = _stack_leaf(next(k), 1, (), hidden, vocab)
    return {"embed": {"qe": qe, "se": se},
            "final_norm": _norm(next(k), hidden),
            "lm_head": {"q": head["q"][0], "s": head["s"][0]},
            "kda": kda, "attention": attention, "layers": every}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole tree on the device from the seed, the first ``layers``
    layers. ``cfg`` is the configuration file (the published keys):
    ``n_routed_experts`` and ``vocab_size`` are this chip's share,
    ``serving.experts_routed`` the router's width."""
    gqa = sum(1 for l in cfg["gqa_layers"] if l < layers)
    linear, head_dim = cfg["linear_attn_config"], cfg["head_dim"]
    held = cfg["n_routed_experts"]
    return _make(
        seed_key(seed), hidden=cfg["hidden_size"],
        inter=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], layers=layers, kda_layers=layers - gqa,
        attn_layers=gqa, dq=cfg["num_attention_heads"] * head_dim,
        dkv=cfg["num_key_value_heads"] * head_dim,
        kda_heads=linear["num_heads"], kda_dim=linear["head_dim"],
        d_conv=linear["short_conv_kernel_size"],
        experts=int(cfg["serving"].get("experts_routed", held)), held=held)
