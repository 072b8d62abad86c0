"""Seeded int8 weights of the GLM-5 block for the correctness check, made by
the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/glm_dsa.py``): kimi_k2's two stacks (``kimi_k2_weights.py`` lists
their leaves), each with the indexer's leaves beside the latent attention's:
``index_wq`` ``[L, q_lora_rank, index heads x index head dim]`` and
``index_wk`` ``[L, hidden, index head dim]`` as ``{"q": int8, "s": f32}``,
the index key's LayerNorm ``index_k_norm`` near 1 and ``index_k_bias`` at
0.1 (so a dropped weight or bias shows), and the heads' weights ``index_w``
``[L, hidden, index heads]`` float32, never quantised.

Every matrix is drawn at ``fan_in^-1/2``, ``index_w`` too: a head's weight
``w_i(t)`` is then of unit spread around 0, so heads weigh in with both
signs and a forward that forgets ``w`` (or its sign) chooses other keys. The
router and its selection bias are kimi_k2's (the bias at 0.1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights as base
from .kimi_k2_weights import _norm, _stack, _stack_leaf

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid


def _with_indexer(key, tree: dict, n: int, *, hidden, index_heads,
                  index_dim) -> dict:
    k = jax.random.split(key, 3)
    return {**tree,
            "index_k_norm": _norm(k[0], n, index_dim),
            "index_k_bias": (0.1 * jax.random.normal(
                k[1], (n, index_dim), jnp.float32)).astype(jnp.bfloat16),
            "index_w": jax.random.normal(
                k[2], (n, hidden, index_heads), jnp.float32) * hidden ** -0.5}


@functools.partial(jax.jit, static_argnames=(
    "hidden", "inter", "moe_inter", "shared", "vocab", "dense_layers",
    "moe_layers", "heads", "q_rank", "kv_rank", "nope", "rope", "v_dim",
    "experts", "held", "index_heads", "index_dim"))
def _make(key, *, hidden, inter, moe_inter, shared, vocab, dense_layers,
          moe_layers, heads, q_rank, kv_rank, nope, rope, v_dim, experts,
          held, index_heads, index_dim):
    k = jax.random.split(key, 10)
    attn = dict(hidden=hidden, q_rank=q_rank, kv_rank=kv_rank, rope=rope,
                heads=heads, qk_dim=nope + rope, nope=nope, v_dim=v_dim)
    indexer = {"index_wq": ((), q_rank, index_heads * index_dim),
               "index_wk": ((), hidden, index_dim)}
    index = dict(hidden=hidden, index_heads=index_heads, index_dim=index_dim)
    dense = _with_indexer(k[7], _stack(k[0], dense_layers, **attn, extra={
        "gate": ((), hidden, inter), "up": ((), hidden, inter),
        "down": ((), inter, hidden), **indexer}), dense_layers, **index)
    si = shared * moe_inter
    layers = _with_indexer(k[8], _stack(k[1], moe_layers, **attn, extra={
        "shared_gate": ((), hidden, si), "shared_up": ((), hidden, si),
        "shared_down": ((), si, hidden),
        "moe_gate": ((held,), hidden, moe_inter),
        "moe_up": ((held,), hidden, moe_inter),
        "moe_down": ((held,), moe_inter, hidden), **indexer}),
        moe_layers, **index)
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
        held=cfg["n_routed_experts"],
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"])
