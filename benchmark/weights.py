"""Seeded int8 weights for the correctness check, made by the benchmark.

Nothing here comes from the program: the values, the scales and the int4
control are the benchmark's own arithmetic. Only the *layout* of the tree is
the program's (``models/llama.py``: stacked layers, ``{"q": int8 [.., in,
out], "s": f32 [.., out]}`` per matmul, ``{"qe", "se"}`` per embedding row),
because that tree is what the program is handed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _quantize(w: jnp.ndarray, axis: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 with one scale per channel: absmax over ``axis`` / 127."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0,
                        1e-12)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis).astype(jnp.float32)


def _matmul_leaf(key, layers: int, fan_in: int, fan_out: int) -> dict:
    def one(k):
        w = jax.random.normal(k, (fan_in, fan_out), jnp.float32) * fan_in ** -0.5
        return _quantize(w, axis=0)

    q, s = jax.lax.map(one, jax.random.split(key, layers))
    return {"q": q, "s": s}


@functools.partial(jax.jit, static_argnames=("hidden", "inter", "vocab",
                                              "layers", "dq", "dkv", "bias"))
def _make(key, *, hidden, inter, vocab, layers, dq, dkv, bias):
    k = iter(jax.random.split(key, 16))
    bf16 = jnp.bfloat16

    def norm(kk, *shape):   # near 1, so a dropped norm weight shows
        return (1.0 + 0.1 * jax.random.normal(kk, shape, jnp.float32)).astype(bf16)

    tree = {
        "attn_norm": norm(next(k), layers, hidden),
        "mlp_norm": norm(next(k), layers, hidden),
        "wq": _matmul_leaf(next(k), layers, hidden, dq),
        "wk": _matmul_leaf(next(k), layers, hidden, dkv),
        "wv": _matmul_leaf(next(k), layers, hidden, dkv),
        "wo": _matmul_leaf(next(k), layers, dq, hidden),
        "gate": _matmul_leaf(next(k), layers, hidden, inter),
        "up": _matmul_leaf(next(k), layers, hidden, inter),
        "down": _matmul_leaf(next(k), layers, inter, hidden),
    }
    if bias:
        for name, width in (("bq", dq), ("bk", dkv), ("bv", dkv)):
            tree[name] = (0.1 * jax.random.normal(next(k), (layers, width),
                                                  jnp.float32)).astype(bf16)
    embed = jax.random.normal(next(k), (vocab, hidden), jnp.float32)
    qe, se = _quantize(embed, axis=1)
    head = _matmul_leaf(next(k), 1, hidden, vocab)
    return {"layers": tree, "embed": {"qe": qe, "se": se},
            "final_norm": norm(next(k), hidden),
            "lm_head": {"q": head["q"][0], "s": head["s"][0]}}


def make_weights(cfg: dict, seed: int, layers: int) -> dict:
    """The whole int8 tree on the device, in one jitted call from the seed.
    ``cfg`` is the published configuration (HF key names)."""
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return _make(seed_key(seed), hidden=cfg["hidden_size"],
                 inter=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                 layers=layers, dq=cfg["num_attention_heads"] * head_dim,
                 dkv=cfg["num_key_value_heads"] * head_dim,
                 bias=bool(cfg.get("attention_bias", False)))


def _is_matmul_leaf(node) -> bool:
    return isinstance(node, dict) and "q" in node and "s" in node


@jax.jit
def to_int4_grid(weights: dict) -> dict:
    """The control: every matmul weight (a ``{"q", "s"}`` node, wherever it
    sits in the tree) rounded to the 15 levels of int4, kept in an int8
    container so the same program runs it. One precision below what the
    configurations state."""
    def leaf(w):
        if not _is_matmul_leaf(w):
            return w
        q4 = jnp.clip(jnp.round(w["q"].astype(jnp.float32) * (7.0 / 127.0)),
                      -7, 7)
        return {**w, "q": q4.astype(jnp.int8), "s": w["s"] * (127.0 / 7.0)}

    return jax.tree_util.tree_map(leaf, weights, is_leaf=_is_matmul_leaf)
