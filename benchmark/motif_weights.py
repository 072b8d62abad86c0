"""Seeded int8 weights of the Motif block for the correctness check, made by
the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/motif.py``): two stacks, ``dense`` (the leading dense layers) and
``layers`` (the expert layers), each with the attention's seven matrices
(``wq_a``, ``wq_b``, ``wkv_a``, ``wkv_b`` over the 16 latent kv GROUPS,
``w_lam``, ``w_gate``, ``wo``) as ``{"q": int8 [L, in, out], "s": f32 [L,
out]}``, its norms near 1, and, float32 and never quantised, the
hyper-connections' maps of its two sub-layers (``mhc_norm`` [L, 2, n C],
``mhc_phi`` [L, 2, n C, 2n + n^2], ``mhc_alpha`` [L, 2, 3], ``mhc_bias`` [L,
2, 2n + n^2]) and PolyNorm's ``poly_coef`` [L, units, 3] and ``poly_bias``
[L, units]; the dense stack's ``gate``/``up``/``down``; the expert stack's
``shared_*``, ``moe_*`` for the experts HELD and the float32 ``router`` over
ALL the experts routed over (no selection bias: the config names none).

Every matrix is drawn at ``fan_in^-1/2``. **The maps are drawn so that a
fault in them shows**: ``mhc_phi`` at ``(n C)^-1/2`` with ``mhc_alpha``
1 +- 0.1 (a token's three maps then move with its streams by about one unit
of logit), ``mhc_bias`` at 0.5, so that no stream is switched off and
``H_res`` is far from a permutation AND from uniform. ``poly_coef`` is 1/3 +-
0.1 and ``poly_bias`` is drawn at 1, so that about a third of the units'
biases lie outside the clamp of 0.5 and the clamp is judged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights as base
from .kimi_k2_weights import _norm, _stack_leaf

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


def _stack(key, n: int, units: int, *, hidden, q_rank, kv_rank, rope, heads,
           signal, groups, qk_dim, nope, v_dim, streams, extra: dict) -> dict:
    k = iter(jax.random.split(key, 24 + len(extra)))
    wide, maps = streams * hidden, 2 * streams + streams * streams
    f32 = jnp.float32
    tree = {"attn_norm": _norm(next(k), n, hidden),
            "q_a_norm": _norm(next(k), n, q_rank),
            "kv_a_norm": _norm(next(k), n, kv_rank),
            "mlp_norm": _norm(next(k), n, hidden),
            "mhc_norm": 1.0 + 0.1 * jax.random.normal(next(k), (n, 2, wide),
                                                      f32),
            "mhc_phi": jax.random.normal(next(k), (n, 2, wide, maps), f32)
            * wide ** -0.5,
            "mhc_alpha": 1.0 + 0.1 * jax.random.normal(next(k), (n, 2, 3),
                                                       f32),
            "mhc_bias": 0.5 * jax.random.normal(next(k), (n, 2, maps), f32),
            "poly_coef": 1 / 3 + 0.1 * jax.random.normal(
                next(k), (n, units, 3), f32),
            "poly_bias": jax.random.normal(next(k), (n, units), f32)}
    shapes = {"wq_a": ((), hidden, q_rank),
              "wq_b": ((), q_rank, heads * qk_dim),
              "wkv_a": ((), hidden, kv_rank + rope),
              "wkv_b": ((), kv_rank, groups * (nope + v_dim)),
              "w_lam": ((), hidden, signal),
              "w_gate": ((), hidden, signal * v_dim),
              "wo": ((), signal * v_dim, hidden), **extra}
    for name, (lead, fan_in, fan_out) in shapes.items():
        tree[name] = _stack_leaf(next(k), n, lead, fan_in, fan_out)
    return tree


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "moe_inter", "shared", "vocab", "dense_layers",
    "moe_layers", "heads", "noise", "groups", "q_rank", "kv_rank", "nope",
    "rope", "v_dim", "streams", "experts", "held"))
def _make(key, *, hidden, inter, moe_inter, shared, vocab, dense_layers,
          moe_layers, heads, noise, groups, q_rank, kv_rank, nope, rope,
          v_dim, streams, experts, held):
    k = jax.random.split(key, 8)
    attn = dict(hidden=hidden, q_rank=q_rank, kv_rank=kv_rank, rope=rope,
                heads=heads, signal=heads - noise, groups=groups,
                qk_dim=nope + rope, nope=nope, v_dim=v_dim, streams=streams)
    dense = _stack(k[0], dense_layers, 1, **attn, extra={
        "gate": ((), hidden, inter), "up": ((), hidden, inter),
        "down": ((), inter, hidden)})
    si = shared * moe_inter
    layers = _stack(k[1], moe_layers, 2, **attn, extra={
        "shared_gate": ((), hidden, si), "shared_up": ((), hidden, si),
        "shared_down": ((), si, hidden),
        "moe_gate": ((held,), hidden, moe_inter),
        "moe_up": ((held,), hidden, moe_inter),
        "moe_down": ((held,), moe_inter, hidden)})
    layers["router"] = jax.random.normal(
        k[2], (moe_layers, hidden, experts), jnp.float32) * hidden ** -0.5
    embed = jax.random.normal(k[4], (vocab, hidden), jnp.float32)
    qe, se = base._quantize(embed, axis=1)
    head = _stack_leaf(k[5], 1, (), hidden, vocab)
    return {"dense": dense, "layers": layers,
            "embed": {"qe": qe, "se": se},
            "final_norm": _norm(k[6], hidden),
            "lm_head": {"q": head["q"][0], "s": head["s"][0]}}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole tree on the device from the seed, ``layers`` deep (the
    leading dense layers first). ``cfg`` is the configuration file: the
    published keys, of which ``num_experts`` and ``vocab_size`` are the
    chip's share (experts held, vocabulary rows held) and
    ``serving.experts_routed`` the router's published width."""
    dense = min(int(cfg["n_dense_first_layers"]), layers)
    return _make(
        seed_key(seed), hidden=cfg["hidden_size"],
        inter=cfg["intermediate_size"],
        moe_inter=cfg["moe_intermediate_size"],
        shared=cfg["num_shared_experts"], vocab=cfg["vocab_size"],
        dense_layers=dense, moe_layers=layers - dense,
        heads=cfg["num_attention_heads"], noise=cfg["num_noise_heads"],
        groups=cfg["num_key_value_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"],
        nope=cfg["head_dim"] - cfg["qk_rope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        streams=cfg["mhc_expansion_rate"],
        experts=cfg["serving"]["experts_routed"], held=cfg["num_experts"])
