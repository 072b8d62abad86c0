"""The plain reference of the Falcon-H1 block: a decoder forward pass in
float32 ``jax.numpy``, one sequence at a time.

Written from the published description (``tiiuae/Falcon-H1-34B-Instruct``
``config.json``, ``model_type: falcon_h1``, and the Hugging Face modelling
code it names). Token embedding times ``embedding_multiplier``; then per
block, on ONE normalised input ``x = RMSNorm(h)``:

- attention: q, k, v projections of ``x · attention_in_multiplier`` (no
  bias), k times ``key_multiplier``, rotary embedding in the rotate-half
  convention at ``rope_theta``, grouped causal attention by the formula,
  output projection times ``attention_out_multiplier``;
- the Mamba-2 mixer: ``[z | xBC | dt] = W_in (x · ssm_in_multiplier)``, each
  part times its entry of ``ssm_multipliers`` (z, x, B, C, dt); a depthwise
  causal conv of width ``mamba_d_conv`` over xBC with bias, then SiLU; per
  head ``Δ_t = softplus(dt_t + dt_bias)``, ``S_t = exp(Δ_t A) S_{t-1} + Δ_t
  x_t B_tᵀ``, ``y_t = S_t C_t + D x_t`` with ``A = −exp(A_log)``, B and C
  shared by the heads of a group — the recurrence itself, one token at a time
  under ``lax.scan``, no chunking; the gate first and then the grouped
  RMSNorm (``mamba_norm_before_gate`` false: ``RMSNorm_groups(y ⊙ SiLU(z))``);
  output projection times ``ssm_out_multiplier``;
- ``h ← h + attention + mixer``; then ``h ← h + mlp_multipliers[1] ·
  W_down(SiLU(mlp_multipliers[0] · W_gate x′) ⊙ W_up x′)``, ``x′ = RMSNorm(h)``.

Final RMSNorm, untied lm-head, logits times ``lm_head_multiplier``.

No cache, no kernel, no batching, no bfloat16: every matrix product at
``highest`` precision. It is handed int8 tensors and their scales and
dequantises them itself. It imports nothing from the program.

Departures from the published forward, each on purpose: weights are int8 with
f32 scales (the configuration's ``assumed``); the published ``time_step_limit``
clamp of Δ to (0, inf) changes nothing and is left out; the published code
keeps activations in bfloat16, this file float32 (it is the reference).

``lower`` turns the reference into a control (correctness.py --control):
``"kv_int8"`` rounds K (after the rotary embedding and its multiplier) and V
to int8 with one scale per token and kv head; ``"fp8"`` rounds the inputs of
every product that are not weights (activations, q, K, V, attention weights,
and the mixer's x, B, C) to float8 e4m3; ``"state_bf16"`` rounds the
recurrent state to bfloat16 after every token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = (None, "kv_int8", "fp8", "state_bf16")


def _dequant(w: dict, layer: int) -> jnp.ndarray:
    return w["q"][layer].astype(jnp.float32) * w["s"][layer][None, :]


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, H, D]; rotate-half convention (first half pairs with second)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _int8_rows(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-12)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@functools.partial(jax.jit, static_argnames=(
    "layers", "heads", "kv_heads", "head_dim", "eps", "theta", "d_ssm",
    "ssm_heads", "ssm_head_dim", "d_state", "groups", "d_conv", "mults",
    "lower"))
def forward_logits(weights, ids, at, *, layers, heads, kv_heads, head_dim,
                   eps, theta, d_ssm, ssm_heads, ssm_head_dim, d_state,
                   groups, d_conv, mults, lower=None):
    """Logits [len(at), V] of one sequence ``ids`` [T] at positions ``at``.
    ``mults`` is the tuple of the published multipliers, in the order of
    :func:`reference_kwargs`."""
    if lower not in CONTROLS:
        raise ValueError(f"unknown control {lower!r}")
    (embedding_m, attn_in_m, attn_out_m, key_m, lm_head_m, ssm_in_m,
     ssm_out_m, ssm_ms, mlp_ms) = mults

    def act(x):     # the input of a product that is not a weight
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        h = h * embedding_m
        mask = pos[None, :] <= pos[:, None]
        group = heads // kv_heads
        gn = groups * d_state
        conv_dim = d_ssm + 2 * gn
        per_group = ssm_heads // groups
        lw = weights["layers"]
        for l in range(layers):
            x = _rms_norm(h, lw["attn_norm"][l], eps)

            # ---- attention
            xa = act(x * attn_in_m)
            q = xa @ _dequant(lw["wq"], l)
            k = (xa @ _dequant(lw["wk"], l)) * key_m
            v = xa @ _dequant(lw["wv"], l)
            q = _rope(q.reshape(T, heads, head_dim), pos, theta)
            k = _rope(k.reshape(T, kv_heads, head_dim), pos, theta)
            v = v.reshape(T, kv_heads, head_dim)
            if lower == "kv_int8":
                k, v = _int8_rows(k), _int8_rows(v)
            q, k, v = act(q), act(k), act(v)
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
            scores = jnp.einsum("ihd,jhd->hij", q, k) / head_dim ** 0.5
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                                   axis=-1)
            attn = jnp.einsum("hij,jhd->ihd", act(probs), v).reshape(
                T, heads * head_dim)
            o_a = (act(attn) @ _dequant(lw["wo"], l)) * attn_out_m

            # ---- the mixer
            proj = act(x * ssm_in_m) @ _dequant(lw["ssm_in"], l)
            z = proj[:, :d_ssm] * ssm_ms[0]
            xbc = proj[:, d_ssm: d_ssm + conv_dim] * jnp.concatenate([
                jnp.full((d_ssm,), ssm_ms[1]), jnp.full((gn,), ssm_ms[2]),
                jnp.full((gn,), ssm_ms[3])])
            dt = proj[:, d_ssm + conv_dim:] * ssm_ms[4]
            # depthwise causal conv: tap d_conv-1 on the current token, zeros
            # before the sequence
            padded = jnp.concatenate(
                [jnp.zeros((d_conv - 1, conv_dim), jnp.float32), xbc])
            conv = lw["conv_b"][l] + sum(
                padded[k_: k_ + T] * lw["conv_w"][l][k_] for k_ in range(d_conv))
            xbc = act(jax.nn.silu(conv))
            xs = xbc[:, :d_ssm].reshape(T, ssm_heads, ssm_head_dim)
            b_mat = xbc[:, d_ssm: d_ssm + gn].reshape(T, groups, d_state)
            c_mat = xbc[:, d_ssm + gn:].reshape(T, groups, d_state)
            b_h = jnp.repeat(b_mat, per_group, axis=1)      # head h: group h // per_group
            c_h = jnp.repeat(c_mat, per_group, axis=1)
            delta = jax.nn.softplus(dt + lw["dt_bias"][l])  # [T, Hs]
            a = -jnp.exp(lw["A_log"][l])

            def token(s, xs_t):
                x_t, b_t, c_t, d_t = xs_t
                s = (jnp.exp(d_t * a)[:, None, None] * s
                     + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
                if lower == "state_bf16":
                    # reduce_precision, not a pair of converts: XLA may elide
                    # f32 -> bf16 -> f32 as excess precision (on the chip it
                    # did: the control read exactly 0)
                    s = jax.lax.reduce_precision(s, exponent_bits=8,
                                                 mantissa_bits=7)
                return s, jnp.einsum("hpn,hn->hp", s, c_t)

            s0 = jnp.zeros((ssm_heads, ssm_head_dim, d_state), jnp.float32)
            _, y = jax.lax.scan(token, s0, (xs, b_h, c_h, delta))
            y = (y + lw["D"][l][None, :, None] * xs).reshape(T, d_ssm)
            g = (y * jax.nn.silu(z)).reshape(T, groups, d_ssm // groups)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            g = g.reshape(T, d_ssm) * lw["ssm_norm"][l]
            o_s = (act(g) @ _dequant(lw["ssm_out"], l)) * ssm_out_m

            h = h + o_a + o_s
            x = act(_rms_norm(h, lw["mlp_norm"][l], eps))
            gate = (x @ _dequant(lw["gate"], l)) * mlp_ms[0]
            up = x @ _dequant(lw["up"], l)
            h = h + (act(jax.nn.silu(gate) * up) @ _dequant(lw["down"], l)) \
                * mlp_ms[1]
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        head = weights["lm_head"]
        return (h @ (head["q"].astype(jnp.float32) * head["s"][None, :])) \
            * lm_head_m


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config."""
    return {
        "layers": layers, "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "d_ssm": cfg["mamba_d_ssm"], "ssm_heads": cfg["mamba_n_heads"],
        "ssm_head_dim": cfg["mamba_d_head"], "d_state": cfg["mamba_d_state"],
        "groups": cfg["mamba_n_groups"], "d_conv": cfg["mamba_d_conv"],
        "mults": (float(cfg["embedding_multiplier"]),
                  float(cfg["attention_in_multiplier"]),
                  float(cfg["attention_out_multiplier"]),
                  float(cfg["key_multiplier"]),
                  float(cfg["lm_head_multiplier"]),
                  float(cfg["ssm_in_multiplier"]),
                  float(cfg["ssm_out_multiplier"]),
                  tuple(float(m) for m in cfg["ssm_multipliers"]),
                  tuple(float(m) for m in cfg["mlp_multipliers"])),
    }


def reference_logits(conf: dict, depth: int):
    """``(weights, ids [T], at [n], lower=None) -> logits [n, V]``."""
    kw = reference_kwargs(conf, depth)

    def logits(w, ids, at, lower=None):
        return forward_logits(w, ids, at, lower=lower, **kw)

    return logits
