"""Seeded int8 weights of the Kimi-K2 block for the correctness check, made
by the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/kimi_k2.py``): two stacks, ``dense`` (the leading dense layers) and
``layers`` (the expert layers), each with the latent attention's five
matrices (``wq_a``, ``wq_b``, ``wkv_a``, ``wkv_b``, ``wo``) as ``{"q": int8
[L, in, out], "s": f32 [L, out]}`` and its norms (``attn_norm``, ``q_a_norm``,
``kv_a_norm``, ``mlp_norm``) near 1; the dense stack's ``gate``/``up``/
``down``; the expert stack's ``shared_*``, ``moe_gate``/``moe_up`` ``[L, held,
H, I]`` and ``moe_down`` ``[L, held, I, H]`` for the experts HELD, the float32
``router`` ``[L, H, E]`` over ALL the experts routed over and its float32
selection bias ``router_bias`` ``[L, E]``. ``weights.to_int4_grid`` walks
every ``{"q", "s"}`` node of it.

Every matrix is drawn at ``fan_in^-1/2``, the router too: a token's logits
over the 384 experts are then of unit spread and its sigmoid scores lie in
0.05-0.95. **The bias is drawn at 0.1**: the 8 largest scores of 384 lie
within a few hundredths of each other, so choosing by ``s + b`` picks other
experts than choosing by ``s`` would for most tokens, while the gate stays
``s`` (a bias that leaked into the weight would move every logits row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights as base

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


def _stack_leaf(key, layers: int, lead: tuple, fan_in: int, fan_out: int
                ) -> dict:
    """``[layers, *lead, fan_in, fan_out]``, a layer at a time (the f32 draw
    of one layer's 12 held experts is 0.7 GB)."""
    def one_layer(k):
        w = jax.random.normal(k, (*lead, fan_in, fan_out),
                              jnp.float32) * fan_in ** -0.5
        return base._quantize(w, axis=len(lead))

    q, s = jax.lax.map(one_layer, jax.random.split(key, layers))
    return {"q": q, "s": s}


def _norm(key, *shape):   # near 1, so a dropped norm weight shows
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


def _stack(key, n: int, *, hidden, q_rank, kv_rank, rope, heads, qk_dim,
           nope, v_dim, extra: dict) -> dict:
    k = iter(jax.random.split(key, 16 + len(extra)))
    tree = {"attn_norm": _norm(next(k), n, hidden),
            "q_a_norm": _norm(next(k), n, q_rank),
            "kv_a_norm": _norm(next(k), n, kv_rank),
            "mlp_norm": _norm(next(k), n, hidden)}
    shapes = {"wq_a": ((), hidden, q_rank),
              "wq_b": ((), q_rank, heads * qk_dim),
              "wkv_a": ((), hidden, kv_rank + rope),
              "wkv_b": ((), kv_rank, heads * (nope + v_dim)),
              "wo": ((), heads * v_dim, hidden), **extra}
    for name, (lead, fan_in, fan_out) in shapes.items():
        tree[name] = _stack_leaf(next(k), n, lead, fan_in, fan_out)
    return tree


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "moe_inter", "shared", "vocab", "dense_layers",
    "moe_layers", "heads", "q_rank", "kv_rank", "nope", "rope", "v_dim",
    "experts", "held"))
def _make(key, *, hidden, inter, moe_inter, shared, vocab, dense_layers,
          moe_layers, heads, q_rank, kv_rank, nope, rope, v_dim, experts,
          held):
    k = jax.random.split(key, 8)
    attn = dict(hidden=hidden, q_rank=q_rank, kv_rank=kv_rank, rope=rope,
                heads=heads, qk_dim=nope + rope, nope=nope, v_dim=v_dim)
    dense = _stack(k[0], dense_layers, **attn, extra={
        "gate": ((), hidden, inter), "up": ((), hidden, inter),
        "down": ((), inter, hidden)})
    si = shared * moe_inter
    layers = _stack(k[1], moe_layers, **attn, extra={
        "shared_gate": ((), hidden, si), "shared_up": ((), hidden, si),
        "shared_down": ((), si, hidden),
        "moe_gate": ((held,), hidden, moe_inter),
        "moe_up": ((held,), hidden, moe_inter),
        "moe_down": ((held,), moe_inter, hidden)})
    layers["router"] = jax.random.normal(
        k[2], (moe_layers, hidden, experts), jnp.float32) * hidden ** -0.5
    layers["router_bias"] = 0.1 * jax.random.normal(
        k[3], (moe_layers, experts), jnp.float32)
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
    published keys, of which ``n_routed_experts`` and ``vocab_size`` are the
    chip's share (experts held, vocabulary rows held) and
    ``serving.experts_routed`` the router's published width."""
    dense = min(int(cfg["first_k_dense_replace"]), layers)
    return _make(
        seed_key(seed), hidden=cfg["hidden_size"],
        inter=cfg["intermediate_size"],
        moe_inter=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"], vocab=cfg["vocab_size"],
        dense_layers=dense, moe_layers=layers - dense,
        heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        experts=cfg["serving"]["experts_routed"],
        held=cfg["n_routed_experts"])


@jax.jit
def router_on_int8_grid(weights: dict) -> dict:
    """The control ``router_int8``: the router's weights rounded to an int8
    grid, one scale an expert, and kept in float32."""
    r = weights["layers"]["router"]
    q, s = base._quantize(r, axis=1)
    lowered = q.astype(jnp.float32) * s[:, None, :]
    return {**weights, "layers": {**weights["layers"], "router": lowered}}
