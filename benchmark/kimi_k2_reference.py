"""The plain reference of the Kimi-K2 block (``model_type: kimi_k2``, the
DeepSeek-V3 block): a forward pass over a whole sequence in float32
``jax.numpy``.

Written from the published ``config.json`` and the modelling code it names;
what neither states is listed in the configuration's ``assumed``. Pre-norm
residual, RMSNorm, no bias anywhere. Per layer:

    x = RMSNorm(h)
    c_q = RMSNorm(x W_dq);  q_h = c_q W_uq,h = [q_nope_h (128) | q_rope_h (64)]
    [c_kv (512) | k_r (64)] = x W_dkv;  c = RMSNorm(c_kv)
    k_r <- RoPE(k_r), ONE head shared by all;  q_rope_h <- RoPE(q_rope_h)
    [k_nope_h (128) | v_h (128)] = c W_ukv,h          for EVERY position
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_r(s)) sigma
    causal softmax;  o_h = sum_s p v_h(s);  h <- h + concat_h(o_h) W_o

    sigma = (128 + 64)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    RoPE's inverse frequencies are YaRN's: theta^(-2i/64), and that over
    ``factor``, blended by a linear ramp between the correction dimensions of
    beta_fast and beta_slow at original_max_position_embeddings; cos and sin
    are scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)

    the first ``first_k_dense_replace`` layers:  h <- h + SwiGLU(x'), x' = RMSNorm(h)
    the others:  s = sigmoid(x' W_g) in float32;  the K experts with the
        largest s + b;  g_e = gamma s_e / sum_{chosen} s
        h <- h + SwiGLU_shared(x') + sum_{e chosen and held} g_e SwiGLU_e(x')

then the final RMSNorm and the untied head over the vocabulary rows held.

**The share.** The weights tree holds the experts ``expert_offset ..
expert_offset + held - 1`` of the router's ``n_routed`` and a slice of the
vocabulary; the gates are normalised over all K chosen, the sum runs over the
chosen experts that are held, and what the others would add is left out, as
in the program (``models/kimi_k2.py``).

No cache, no kernel, no batching, no bfloat16, and attention is NOT absorbed:
K and V are expanded from the compressed row for every position. One
sequence at a time, every matrix product at ``highest`` precision, every
held expert computed for every token and combined by a gate that is zero off
the chosen. It is handed int8 tensors and their scales and dequantises them
itself. It imports nothing from the program.

``choices`` [expert layers, T, K] hands the forward the experts to use in
place of its own K largest (the adapter passes the program's:
``adapters/kimi_k2.py`` says why); the gates stay the reference's own scores
over them, and the forward also returns, per position, how far the
lowest-scored of them lies under the reference's own K-th largest ``s + b``.
``lower`` is a control, the same forward with one thing kept one precision
below what the configuration states: ``"fp8"`` rounds the inputs of every
matrix product that are not weights to float8 e4m3; ``"latent_int8"`` rounds
what the cache would hold, ``c`` (after its norm) and ``k_r`` (after the
rotation), to int8 with one scale a token each, as an int8 latent page
would. ``sigma_scale`` multiplies ``sigma`` (1; a test leaves ``m^2`` out
with it).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .reference import _dequant, _int8_rows, _rms_norm


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """[dim/2] float64: the published ``yarn_find_correction_range`` and
    ``yarn_linear_ramp_mask``."""
    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: the pair keeps its frequency
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    return extra / factor * (1.0 - keep) + extra * keep


def softmax_scale(qk_head_dim: int, factor: float, mscale_all_dim: float
                  ) -> float:
    scale = qk_head_dim ** -0.5
    if factor > 1.0 and mscale_all_dim:
        scale *= yarn_mscale(factor, mscale_all_dim) ** 2
    return scale


def _rope(x, positions, inv_freq, table_scale):
    """x [T, heads, D]; rotate-half convention (first half pairs with the
    second)."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return (x * cos + rotated * sin) * table_scale


def _leaf(w: dict, *index) -> jnp.ndarray:
    return _dequant({"q": w["q"][index], "s": w["s"][index]})


@functools.partial(jax.jit, static_argnames=(
    "dense_layers", "moe_layers", "heads", "nope", "rope", "v_dim", "rank",
    "eps", "theta", "yarn", "top_k", "gamma", "expert_offset", "lower",
    "sigma_scale"))
def forward_logits(weights, ids, at, choices=None, *, dense_layers,
                   moe_layers, heads, nope, rope, v_dim, rank, eps, theta,
                   yarn, top_k, gamma, expert_offset, lower=None,
                   sigma_scale=1.0):
    """(logits [len(at), V held], shortfall [moe_layers, T]) of one sequence
    ``ids`` [T] at positions ``at``. ``yarn`` = (factor, original, beta_fast,
    beta_slow, mscale, mscale_all_dim)."""
    if lower not in (None, "fp8", "latent_int8"):
        raise ValueError(f"unknown control {lower!r}")

    def act(x):     # the input of a matrix product
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    factor, original, beta_fast, beta_slow, mscale, mscale_all = yarn
    inv_freq = yarn_inv_freq(rope, theta, factor, original, beta_fast,
                             beta_slow)
    table_scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    sigma = softmax_scale(nope + rope, factor, mscale_all) * sigma_scale

    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        causal = pos[None, :] <= pos[:, None]

        def attention(lw, l, h):
            x = act(_rms_norm(h, lw["attn_norm"][l], eps))
            c_q = act(_rms_norm(x @ _leaf(lw["wq_a"], l), lw["q_a_norm"][l],
                                eps))
            q = (c_q @ _leaf(lw["wq_b"], l)).reshape(T, heads, nope + rope)
            ckv = x @ _leaf(lw["wkv_a"], l)
            c = _rms_norm(ckv[:, :rank], lw["kv_a_norm"][l], eps)
            k_r = _rope(ckv[:, None, rank:], pos, inv_freq, table_scale)
            q_rope = _rope(q[..., nope:], pos, inv_freq, table_scale)
            if lower == "latent_int8":
                c, k_r = _int8_rows(c), _int8_rows(k_r)
            kv = (act(c) @ _leaf(lw["wkv_b"], l)).reshape(
                T, heads, nope + v_dim)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_r, (T, heads, rope))], -1)
            qq = jnp.concatenate([q[..., :nope], q_rope], -1)
            scores = jnp.einsum("ihd,jhd->hij", act(qq), act(k)) * sigma
            probs = jax.nn.softmax(
                jnp.where(causal[None], scores, -jnp.inf), -1)
            o = jnp.einsum("hij,jhd->ihd", act(probs), act(kv[..., nope:]))
            return h + act(o.reshape(T, -1)) @ _leaf(lw["wo"], l)

        def swiglu(x, gate, up, down):
            return act(jax.nn.silu(x @ gate) * (x @ up)) @ down

        dw = weights["dense"]
        for l in range(dense_layers):
            h = attention(dw, l, h)
            x = act(_rms_norm(h, dw["mlp_norm"][l], eps))
            h = h + swiglu(x, _leaf(dw["gate"], l), _leaf(dw["up"], l),
                           _leaf(dw["down"], l))

        lw = weights["layers"]
        held = lw["moe_gate"]["q"].shape[1]
        shortfalls = []
        for l in range(moe_layers):
            h = attention(lw, l, h)
            x = act(_rms_norm(h, lw["mlp_norm"][l], eps))
            s = jax.nn.sigmoid(x @ lw["router"][l].astype(jnp.float32))
            biased = s + lw["router_bias"][l].astype(jnp.float32)  # [T, E]
            own_top, own = jax.lax.top_k(biased, top_k)
            chosen = own if choices is None else choices[l]
            picked = jnp.take_along_axis(biased, chosen, axis=1)
            shortfalls.append(own_top[:, -1] - picked.min(axis=1))
            s_top = jnp.take_along_axis(s, chosen, axis=1)
            gates = jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(
                gamma * s_top / s_top.sum(axis=1, keepdims=True))   # [T, E]

            def expert(acc, e, l=l, x=x, gates=gates):
                g, u, d = (_dequant({"q": lw[n]["q"][l][e],
                                     "s": lw[n]["s"][l][e]})
                           for n in ("moe_gate", "moe_up", "moe_down"))
                gate = jax.lax.dynamic_index_in_dim(
                    gates, expert_offset + e, axis=1, keepdims=True)
                return acc + gate * swiglu(x, g, u, d), None

            routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                                     jnp.arange(held))
            h = h + routed + swiglu(
                x, _leaf(lw["shared_gate"], l), _leaf(lw["shared_up"], l),
                _leaf(lw["shared_down"], l))
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        return h @ _dequant(weights["lm_head"]), jnp.stack(shortfalls)


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config,
    at a depth of ``layers`` (the leading dense layers first)."""
    dense = min(int(cfg["first_k_dense_replace"]), layers)
    rs = cfg["rope_scaling"]
    return {"dense_layers": dense, "moe_layers": layers - dense,
            "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v_dim": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
            "yarn": (float(rs["factor"]),
                     int(rs["original_max_position_embeddings"]),
                     float(rs["beta_fast"]), float(rs["beta_slow"]),
                     float(rs["mscale"]), float(rs["mscale_all_dim"])),
            "top_k": int(cfg["num_experts_per_tok"]),
            "gamma": float(cfg["routed_scaling_factor"]),
            "expert_offset": int(cfg["serving"].get("expert_offset", 0))}
