"""Seeded int8 weights of the Ouro block for the correctness check, made by
the benchmark.

Nothing here comes from the program but the *layout* of the tree
(``models/ouro.py``): ONE stack ``layers`` of the seven matrices ``wq wk wv
wo gate up down`` as ``{"q": int8 [L, in, out], "s": f32 [L, out]}`` and FOUR
norm gains a layer (``attn_norm``, ``attn_post_norm``, ``mlp_norm``,
``mlp_post_norm``), the embedding per row, ``final_norm``, the head, and the
float32 ``exit_gate`` ``{"w": [H], "b": []}``.

**The gains are drawn so that a fault in them shows**: all four of a layer,
and the final norm that every pass ends in, at 1 +- 0.1 (``weights.py``'s
spread), never at exactly 1, so a dropped or a swapped norm moves the logits.
``w_exit`` is drawn at ``fan_in^-1/2`` and ``b_exit`` at 0.5: the normed
hidden has unit rows, so ``w . h`` is about one unit and ``lam`` ranges over
most of (0, 1), where a wrong pass or a missing norm moves it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights as base

seed_key = base.seed_key
to_int4_grid = base.to_int4_grid

NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


@functools.partial(jax.jit, static_argnames=("hidden", "inter", "vocab",
                                              "layers", "dq", "dkv"))
def _make(key, *, hidden, inter, vocab, layers, dq, dkv):
    k = iter(jax.random.split(key, 20))
    bf16 = jnp.bfloat16

    def norm(kk, *shape):
        return (1.0 + 0.1 * jax.random.normal(kk, shape, jnp.float32)
                ).astype(bf16)

    tree = {name: norm(next(k), layers, hidden) for name in NORMS}
    for name, fan_in, fan_out in (
            ("wq", hidden, dq), ("wk", hidden, dkv), ("wv", hidden, dkv),
            ("wo", dq, hidden), ("gate", hidden, inter), ("up", hidden, inter),
            ("down", inter, hidden)):
        tree[name] = base._matmul_leaf(next(k), layers, fan_in, fan_out)
    embed = jax.random.normal(next(k), (vocab, hidden), jnp.float32)
    qe, se = base._quantize(embed, axis=1)
    head = base._matmul_leaf(next(k), 1, hidden, vocab)
    return {"layers": tree, "embed": {"qe": qe, "se": se},
            "final_norm": norm(next(k), hidden),
            "lm_head": {"q": head["q"][0], "s": head["s"][0]},
            "exit_gate": {
                "w": jax.random.normal(next(k), (hidden,), jnp.float32)
                * hidden ** -0.5,
                "b": 0.5 * jax.random.normal(next(k), (), jnp.float32)}}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole int8 tree on the device, in one jitted call from the seed.
    ``cfg`` is the published configuration (HF key names); ``layers`` the
    layers of the stack (each runs ``total_ut_steps`` times)."""
    head_dim = cfg["head_dim"]
    return _make(seed_key(seed), hidden=cfg["hidden_size"],
                 inter=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                 layers=layers, dq=cfg["num_attention_heads"] * head_dim,
                 dkv=cfg["num_key_value_heads"] * head_dim)
