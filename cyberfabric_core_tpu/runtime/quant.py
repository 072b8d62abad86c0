"""Weight-only quantization: int8 (W8) and int4 (W4).

Purpose: HBM. Decode throughput is weight-bandwidth-bound and a v5e chip holds
16 GB — Llama-3-8B bf16 (16.1 GB) doesn't fit one chip, W8 (8.1 GB) does, and
W4 (~4.3 GB) halves decode bytes again. Symmetric per-output-channel scales;
the intN→bf16 convert sits inside the dot's operand so XLA fuses it into the
matmul read (weights stream from HBM narrow — XLA:TPU stores s4 packed two to
a byte). Norm weights stay bf16 (tiny, and their statistics are
precision-sensitive). W4 per-CHANNEL scaling is coarse for real checkpoints
(group-wise scales are the usual fix; synthetic-weight benching is
insensitive) — it is the bandwidth experiment, W8 the accuracy default.

Quantized leaf representation: {"q": int8 [..., in, out], "s": f32 [..., out]}
(leading stacked-layer/expert dims preserved). models/llama.py's matmul helpers
accept either a plain array or this dict.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

#: layers-tree leaves that are matmul weights (contraction on axis -2)
_MATMUL_LEAVES = {"wq", "wk", "wv", "wo", "gate", "up", "down",
                  "moe_gate", "moe_up", "moe_down",
                  # kimi_k2: latent attention's projections and the shared
                  # expert; its norms stay as they are, its router and the
                  # router's selection bias float32
                  "wq_a", "wq_b", "wkv_a", "wkv_b",
                  "shared_gate", "shared_up", "shared_down",
                  # nemotron_h: the two projections around the experts' latent
                  "latent_down", "latent_up",
                  # solar_open2: the attention layers' output gate; a kda
                  # layer's q, k, v, o under the names above, the decay's and
                  # the gate's low-rank pairs and β. Its conv taps, A_log,
                  # dt_bias and the head norm's weight stay f32
                  "w_gate", "f_a", "f_b", "g_a", "g_b", "w_beta",
                  # motif: the differential gate a signal head; its
                  # hyper-connections' maps, PolyNorm's coefficients and the
                  # router stay float32
                  "w_lam",
                  # glm_moe_dsa: the indexer's query and key projections;
                  # its weight a head (index_w) stays float32 like a router,
                  # the index key's LayerNorm as it is
                  "index_wq", "index_wk",
                  # falcon_h1's mixer: W_in and W_out like any matrix; its
                  # conv, A_log, D, dt_bias and norm weights stay f32
                  "ssm_in", "ssm_out"}


def quant_bits(quantization: str) -> int | None:
    """EngineConfig.quantization string → bit width (None = unquantized).
    The ONE mapping every engine/export/load path shares — unknown strings
    fail here instead of silently serving bf16."""
    table = {"none": None, "": None, "int8": 8, "int4": 4}
    if quantization not in table:
        raise ValueError(f"unknown quantization {quantization!r} "
                         f"(supported: {sorted(k for k in table if k)})")
    return table[quantization]


def quantize_weight(w: jnp.ndarray, bits: int = 8) -> dict[str, jnp.ndarray]:
    """Symmetric per-output-channel intN: scale over the contraction axis (-2)."""
    qmax = {8: 127, 4: 7}[bits]
    qdtype = jnp.int8 if bits == 8 else jnp.int4
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)  # [..., 1, out]
    scale = jnp.maximum(absmax / qmax, 1e-12)
    q = jnp.clip(jnp.round(wf / scale), -qmax, qmax).astype(qdtype)
    return {"q": q, "s": scale[..., 0, :].astype(jnp.float32)}


def dequantize_weight(wq: dict[str, jnp.ndarray], dtype=jnp.bfloat16) -> jnp.ndarray:
    return (wq["q"].astype(jnp.float32) * wq["s"][..., None, :]).astype(dtype)


def quantize_llama_params(params: dict[str, Any], bits: int = 8) -> dict[str, Any]:
    """Quantize every matmul weight + lm_head + embed; norms stay as-is.
    The embed table stays int8 even at bits=4 (gather from s4 is not a
    bandwidth-critical path and per-row int8 is accuracy-safe)."""
    # what is not named below stays as it is (the final norm; ouro's float32
    # exit gate)
    out: dict[str, Any] = dict(params)
    out["embed"] = _quantize_embed(params["embed"])
    if "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"], bits)
    # "layers", and where the stack is not one repeated layer the stacks
    # beside it: kimi_k2's leading "dense" layers, granite_hybrid's "mamba"
    # and "attention" layers (the mixer's conv, A_log, D, dt_bias and norm
    # stay float32; the router float32), nemotron_h's "moe" layers,
    # solar_open2's "kda" layers, laguna's "full" and "window" attention
    # layers (its gate a head is ``w_gate``)
    for stack in ("dense", "mamba", "kda", "attention", "full", "window",
                  "moe", "layers"):
        if stack in params:
            out[stack] = {
                # norms, router (tiny + precision-sensitive) stay as they are
                name: quantize_weight(w, bits) if name in _MATMUL_LEAVES else w
                for name, w in params[stack].items()}
    return out


def _quantize_embed(embed: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Embedding rows: per-ROW scales (gather then rescale)."""
    ef = embed.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(ef), axis=-1, keepdims=True)  # [V, 1]
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(ef / scale), -127, 127).astype(jnp.int8)
    # distinct keys ("qe"/"se") mark per-ROW scaling; a string marker would break
    # jit argument handling (every pytree leaf must be an array)
    return {"qe": q, "se": scale[:, 0].astype(jnp.float32)}


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "bits"))
def _init_quantized_leaf(key: jax.Array, shape: tuple[int, ...], dtype,
                         bits: int) -> dict[str, jnp.ndarray]:
    """Sample one matmul weight and quantize it, one slice of the leading
    (stacked-layer) axis at a time: the only full-size array is the intN
    result. Op by op, the f32 copies ``quantize_weight`` makes of a 7B
    model's [32, 4096, 14336] leaf are 7.5 GB each and exhaust a 16 GB chip."""
    scale = jnp.asarray(1.0 / shape[-2] ** 0.5, dtype)

    def one(k, shp):
        return quantize_weight(jax.random.normal(k, shp, dtype) * scale, bits)

    if len(shape) == 2:
        return one(key, shape)
    return jax.lax.map(lambda k: one(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def init_params_quantized(cfg, key: jax.Array, dtype=jnp.bfloat16,
                          bits: int = 8) -> dict[str, Any]:
    """Synthetic-weight init directly into W8/W4: peak HBM is the intN tree
    plus one layer's slice of one leaf, so a 7-8B model inits inside one
    v5e chip."""
    from ..models import decoder_module

    lay_out = getattr(decoder_module(cfg), "init_params_with", None)
    if lay_out is not None:
        # a tree that is not the llama family's: the model module lays it
        # out, every matrix made here, a layer's slice at a time
        def embed(k, shape):
            return jax.jit(_quantize_embed)(
                jax.random.normal(k, shape, dtype)
                * jnp.asarray(shape[-1] ** -0.5, dtype))

        return lay_out(
            cfg, key, dtype,
            lambda k, shape: _init_quantized_leaf(k, tuple(shape),
                                                  jnp.dtype(dtype), bits),
            embed)
    H, I, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    Dq, Dkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 16))

    def w(*shape):
        return _init_quantized_leaf(next(keys), shape, jnp.dtype(dtype), bits)

    layers: dict[str, Any] = {
        "attn_norm": jnp.ones((L, H), dtype),
        "wq": w(L, H, Dq), "wk": w(L, H, Dkv), "wv": w(L, H, Dkv),
        "wo": w(L, Dq, H),
        "mlp_norm": jnp.ones((L, H), dtype),
    }
    if cfg.attention_bias:  # Qwen2-family; biases stay unquantized (tiny)
        layers.update({
            "bq": jax.random.normal(next(keys), (L, Dq), dtype) * 0.02,
            "bk": jax.random.normal(next(keys), (L, Dkv), dtype) * 0.02,
            "bv": jax.random.normal(next(keys), (L, Dkv), dtype) * 0.02,
        })
    if cfg.qk_norm:
        layers.update({"q_norm": jnp.ones((L, cfg.head_dim), dtype),
                       "k_norm": jnp.ones((L, cfg.head_dim), dtype)})
    if cfg.num_experts > 0:
        E = cfg.num_experts
        # never quantized
        router_dtype = jnp.float32 if cfg.router_float32 else dtype
        layers["router"] = (jax.random.normal(next(keys), (L, H, E),
                                              router_dtype)
                            * jnp.asarray(H ** -0.5, router_dtype))
        layers.update({"moe_gate": w(L, E, H, I), "moe_up": w(L, E, H, I),
                       "moe_down": w(L, E, I, H)})
    else:
        layers.update({"gate": w(L, H, I), "up": w(L, H, I), "down": w(L, I, H)})
    if cfg.has_state:  # falcon_h1: the mixer beside attention
        from ..models.falcon_h1 import init_mixer_small

        layers.update({"ssm_in": w(L, H, cfg.ssm_proj_dim),
                       "ssm_out": w(L, cfg.ssm_inner, H),
                       **init_mixer_small(cfg, next(keys))})

    embed_full = (jax.random.normal(next(keys), (V, H), dtype)
                  * jnp.asarray(H ** -0.5, dtype))
    params: dict[str, Any] = {
        # jitted: op by op, the f32 copies of a [261120, 5120] table are
        # 5 GB each beside 7 GB of layers (falcon-h1-34b-16l on a v5e)
        "embed": jax.jit(_quantize_embed)(embed_full),
        "final_norm": jnp.ones((H,), dtype),
        "layers": layers,
    }
    del embed_full
    if not cfg.tie_embeddings:
        params["lm_head"] = w(H, V)
    return params


def quantized_bytes(params: dict[str, Any]) -> int:
    total = 0
    for leaf in jax.tree.leaves(params):
        if hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += leaf.size * leaf.dtype.itemsize
    return total
