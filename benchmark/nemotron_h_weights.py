"""Seeded int8 weights of the Nemotron-H stack for the correctness check, made
by the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/nemotron_h.py``): ``layers`` over ALL layers holds what every layer
has, its ``norm`` (near 1); beside it one stack for each kind, over the
layers of that kind at the judged depth. ``mamba`` (``ssm_in``, ``ssm_out``
as ``{"q": int8 [Lm, in, out], "s": f32 [Lm, out]}``; the conv's taps and
bias, ``A_log``, ``D``, ``dt_bias`` and the gated norm's weight float32),
``attention`` (``wq``, ``wk``, ``wv``, ``wo``) and ``moe``: the float32
``router`` ``[Le, H, E]`` over ALL the routed experts and its selection bias
``router_bias`` ``[Le, E]``, the latent's two projections ``latent_down``
``[Le, H, W]`` and ``latent_up`` ``[Le, W, H]``, the shared expert
``shared_up`` / ``shared_down``, and the experts HELD, two matrices each and
no gate: ``moe_up`` ``[Le, held, W, I]``, ``moe_down`` ``[Le, held, I, W]``.
The embedding is ``{"qe", "se"}`` over the vocabulary rows held, the head
(untied) ``lm_head`` over the same rows. ``weights.to_int4_grid`` walks every
``{"q", "s"}`` node of it.

Every matrix is drawn at ``fan_in^-1/2``, the router too: with the
embedding's rows of unit size every branch then adds about what the residual
holds (a squared ReLU of a unit normal has an rms of 1.2; the held quarter of
22 gates that sum to 5 gives the routed part about 0.6), so no branch hides
behind another in a comparison of logits. **The bias is drawn at 0.1**, as
kimi's: the 22 largest scores of 512 lie about 0.003 apart, so a bias of
that size reorders them, and one that leaked into the gate would move every
logits row. The mixer's small leaves as ``falcon_h1_weights.py`` draws them
(decays neither 0 nor 1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import weights as base
# a stacked int8 leaf drawn a layer at a time (the f32 draw of one layer's 128
# held experts is 1.4 GB a matrix), and a norm near 1
from .granite_hybrid_weights import _norm, _stack_leaf

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "latent", "shared", "vocab", "layers", "mamba_layers",
    "attn_layers", "moe_layers", "dq", "dkv", "d_inner", "ssm_heads",
    "conv_dim", "d_conv", "experts", "held"))
def _make(key, *, hidden, inter, latent, shared, vocab, layers, mamba_layers,
          attn_layers, moe_layers, dq, dkv, d_inner, ssm_heads, conv_dim,
          d_conv, experts, held):
    k = iter(jax.random.split(key, 32))
    step = jnp.exp(jax.random.uniform(
        next(k), (mamba_layers, ssm_heads), jnp.float32, math.log(1e-3),
        math.log(1e-1)))
    mamba = {
        "ssm_in": _stack_leaf(next(k), mamba_layers, (), hidden,
                              d_inner + conv_dim + ssm_heads),
        "ssm_out": _stack_leaf(next(k), mamba_layers, (), d_inner, hidden),
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
        "wq": _stack_leaf(next(k), attn_layers, (), hidden, dq),
        "wk": _stack_leaf(next(k), attn_layers, (), hidden, dkv),
        "wv": _stack_leaf(next(k), attn_layers, (), hidden, dkv),
        "wo": _stack_leaf(next(k), attn_layers, (), dq, hidden),
    }
    moe = {
        "router": jax.random.normal(next(k), (moe_layers, hidden, experts),
                                    jnp.float32) * hidden ** -0.5,
        "router_bias": 0.1 * jax.random.normal(
            next(k), (moe_layers, experts), jnp.float32),
        "latent_down": _stack_leaf(next(k), moe_layers, (), hidden, latent),
        "latent_up": _stack_leaf(next(k), moe_layers, (), latent, hidden),
        "shared_up": _stack_leaf(next(k), moe_layers, (), hidden, shared),
        "shared_down": _stack_leaf(next(k), moe_layers, (), shared, hidden),
        "moe_up": _stack_leaf(next(k), moe_layers, (held,), latent, inter),
        "moe_down": _stack_leaf(next(k), moe_layers, (held,), inter, latent),
    }
    embed = jax.random.normal(next(k), (vocab, hidden), jnp.float32)
    qe, se = base._quantize(embed, axis=1)
    head = _stack_leaf(next(k), 1, (), hidden, vocab)
    return {"embed": {"qe": qe, "se": se},
            "final_norm": _norm(next(k), hidden),
            "lm_head": {"q": head["q"][0], "s": head["s"][0]},
            "layers": {"norm": _norm(next(k), layers, hidden)},
            "mamba": mamba, "attention": attention, "moe": moe}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole tree on the device from the seed, the first ``layers``
    layers of ``hybrid_override_pattern``. ``cfg`` is the configuration file
    (the published keys): ``n_routed_experts`` and ``vocab_size`` are this
    chip's share, ``serving.experts_routed`` the router's width."""
    kinds = cfg["hybrid_override_pattern"][:layers]
    head_dim = cfg["head_dim"]
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = d_inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    held = cfg["n_routed_experts"]
    return _make(
        seed_key(seed), hidden=cfg["hidden_size"],
        inter=cfg["moe_intermediate_size"], latent=cfg["moe_latent_size"],
        shared=cfg["moe_shared_expert_intermediate_size"],
        vocab=cfg["vocab_size"], layers=layers,
        mamba_layers=kinds.count("M"), attn_layers=kinds.count("*"),
        moe_layers=kinds.count("E"),
        dq=cfg["num_attention_heads"] * head_dim,
        dkv=cfg["num_key_value_heads"] * head_dim, d_inner=d_inner,
        ssm_heads=cfg["mamba_num_heads"], conv_dim=conv_dim,
        d_conv=cfg["conv_kernel"],
        experts=int(cfg["serving"].get("experts_routed", held)), held=held)
