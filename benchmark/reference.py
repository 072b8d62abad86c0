"""The plain reference: a decoder forward pass in float32 ``jax.numpy``.

Written from the published descriptions (Mistral-7B-v0.1 and Qwen2-7B
``config.json`` and the Hugging Face modelling code they name): token
embedding, then per layer RMSNorm -> q/k/v projections (+ bias where the
config says so) -> rotary embedding in the rotate-half convention -> grouped
causal attention with an optional sliding window -> output projection ->
residual -> RMSNorm -> SwiGLU -> residual; final RMSNorm; untied lm-head.

No cache, no kernel, no batching, no bfloat16: one sequence at a time, every
matrix product at ``highest`` precision. It is handed int8 tensors and their
scales and dequantises them itself. It imports nothing from the program.

``lower`` turns the reference into a control (correctness.py --control): the
same forward with one thing kept one precision below what the configurations
state (bfloat16 activations and K/V): ``"kv_int8"`` rounds K (after the
rotary embedding) and V to int8 with one scale per token and kv head, as a
page pool of int8 would hold them; ``"fp8"`` rounds the inputs of every
matrix product that are not weights (the activations, and q, K, V and the
attention weights) to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _dequant(w: dict) -> jnp.ndarray:
    return w["q"].astype(jnp.float32) * w["s"][..., None, :]


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, H, D]; rotate-half convention (first half pairs with second)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]   # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _int8_rows(x):
    """Round to int8 with one absmax scale per leading index (token, head)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-12)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@functools.partial(jax.jit, static_argnames=(
    "layers", "heads", "kv_heads", "head_dim", "eps", "theta", "window",
    "lower"))
def forward_logits(weights, ids, at, *, layers, heads, kv_heads, head_dim,
                   eps, theta, window, lower=None):
    """Logits [len(at), V] of one sequence ``ids`` [T] at positions ``at``."""
    if lower not in (None, "kv_int8", "fp8"):
        raise ValueError(f"unknown control {lower!r}")

    def act(x):     # the input of a matrix product
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        i, j = pos[:, None], pos[None, :]
        mask = j <= i
        if window is not None:
            mask = mask & (j > i - window)
        group = heads // kv_heads
        lw = weights["layers"]
        for l in range(layers):
            x = act(_rms_norm(h, lw["attn_norm"][l], eps))
            q = x @ _dequant({"q": lw["wq"]["q"][l], "s": lw["wq"]["s"][l]})
            k = x @ _dequant({"q": lw["wk"]["q"][l], "s": lw["wk"]["s"][l]})
            v = x @ _dequant({"q": lw["wv"]["q"][l], "s": lw["wv"]["s"][l]})
            if "bq" in lw:
                q = q + lw["bq"][l].astype(jnp.float32)
                k = k + lw["bk"][l].astype(jnp.float32)
                v = v + lw["bv"][l].astype(jnp.float32)
            q = _rope(q.reshape(T, heads, head_dim), pos, theta)
            k = _rope(k.reshape(T, kv_heads, head_dim), pos, theta)
            v = v.reshape(T, kv_heads, head_dim)
            if lower == "kv_int8":
                k, v = _int8_rows(k), _int8_rows(v)
            q, k, v = act(q), act(k), act(v)
            k = jnp.repeat(k, group, axis=1)     # query head h reads kv head h // group
            v = jnp.repeat(v, group, axis=1)
            scores = jnp.einsum("ihd,jhd->hij", q, k) / head_dim ** 0.5
            scores = jnp.where(mask[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("hij,jhd->ihd", act(probs), v).reshape(T, heads * head_dim)
            h = h + act(attn) @ _dequant({"q": lw["wo"]["q"][l], "s": lw["wo"]["s"][l]})
            x = act(_rms_norm(h, lw["mlp_norm"][l], eps))
            gate = x @ _dequant({"q": lw["gate"]["q"][l], "s": lw["gate"]["s"][l]})
            up = x @ _dequant({"q": lw["up"]["q"][l], "s": lw["up"]["s"][l]})
            h = h + act(jax.nn.silu(gate) * up) @ _dequant(
                {"q": lw["down"]["q"][l], "s": lw["down"]["s"][l]})
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        return h @ _dequant(weights["lm_head"])


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config."""
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    window = cfg.get("sliding_window")
    if cfg.get("use_sliding_window") is False:   # Qwen2 publishes a window it does not use
        window = None
    return {"layers": layers, "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head_dim": head_dim,
            "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
            "window": window}
