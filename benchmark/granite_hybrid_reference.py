"""The plain reference of the Granite-4.0-H stack (``model_type:
granitemoehybrid``): a forward pass over one whole sequence in float32
``jax.numpy``.

Written from the published ``config.json`` of
``ibm-granite/granite-4.0-h-small`` and the Hugging Face modelling code that
``model_type`` names. RMSNorm (``rms_norm_eps``), pre-norm residual, no bias
but the conv's. With ``kind = layer_types[l]``:

    h_0 = embedding_multiplier · Embed(ids)
    x = RMSNorm(h)
    mamba:      [z | xBC | dt] = x W_in      (d_inner | d_inner + 2 G N | heads)
                xBC = SiLU(conv1d_K(xBC) + b)  (depthwise, causal, zeros before)
                Δ = softplus(dt + dt_bias);  A = −exp(A_log)
                S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t B_tᵀ;  y_t = S_t C_t + D x_t
                m = W_out · RMSNorm(y ⊙ SiLU(z))       (gate first, ONE group)
    attention:  q, k, v = x W_q, x W_k, x W_v          (NO rotary: "nope")
                m = W_o · softmax(q kᵀ · attention_multiplier + causal) v
    h ← h + residual_multiplier · m
    x′ = RMSNorm(h);  ℓ = x′ W_g;  the K largest of ℓ;  g = softmax over those K
    f = Σ_{e chosen} g_e W2_e(SiLU(W1a_e x′) ⊙ W1b_e x′)  +  Ws2(SiLU(Ws1a x′) ⊙ Ws1b x′)
    h ← h + residual_multiplier · f
    logits = (RMSNorm(h) Embedᵀ) / logits_scaling       (tied head)

The recurrence is the recurrence itself, one token at a time under
``lax.scan``, no chunking; attention is the formula; every expert is computed
for every token, one expert at a time in a loop, and combined by a gate that
is zero off the chosen. No cache, no kernel, no batching, no bfloat16: every
matrix product at ``highest`` precision. It is handed int8 tensors and their
scales and dequantises them itself. It imports nothing from the program.

Departures from the published forward, each on purpose (the configuration's
``assumed`` lists them too): weights are int8 with f32 scales; the published
code stores an expert's two input matrices fused (``input_linear``, gate then
up) and the tree here holds them apart (``moe_gate``, ``moe_up``), likewise
the shared MLP's; the published ``time_step_limit`` clamp of Δ to (0, inf)
changes nothing and is left out; the published code keeps activations in
bfloat16, this file float32 (it is the reference).

``choices`` [layers, T, K] hands the forward the experts to use in place of
its own K largest (the adapter passes the program's:
``adapters/granite_hybrid.py`` says why); the gates stay the reference's own
softmax over ITS logits of them, and the forward also returns, per position,
how far the lowest-scored of them lies under the reference's own K-th
largest logit. ``lower`` is a control, the same forward with one thing kept
one precision below what the configuration states: ``"fp8"`` rounds the
inputs of every product that are not weights (activations, q, K, V,
attention weights, the mixer's x, B, C) to float8 e4m3; ``"kv_int8"`` rounds K
and V to int8 with one scale per token and kv head; ``"state_bf16"`` rounds
the recurrent state to bfloat16 after every token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = (None, "fp8", "kv_int8", "state_bf16")


def _dequant(w: dict, *index) -> jnp.ndarray:
    q, s = w["q"][index], w["s"][index]
    return q.astype(jnp.float32) * s[..., None, :]


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _int8_rows(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-12)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@functools.partial(jax.jit, static_argnames=(
    "layer_types", "heads", "kv_heads", "head_dim", "eps", "d_inner",
    "ssm_heads", "ssm_head_dim", "d_state", "groups", "d_conv", "top_k",
    "mults", "lower"))
def forward_logits(weights, ids, at, choices=None, *, layer_types, heads,
                   kv_heads, head_dim, eps, d_inner, ssm_heads, ssm_head_dim,
                   d_state, groups, d_conv, top_k, mults, lower=None):
    """(logits [len(at), V], shortfall [layers, T]) of one sequence ``ids``
    [T] at positions ``at``. ``mults`` = (embedding_multiplier,
    residual_multiplier, attention_multiplier, logits_scaling)."""
    if lower not in CONTROLS:
        raise ValueError(f"unknown control {lower!r}")
    embedding_m, residual_m, attention_m, logits_div = mults

    def act(x):     # the input of a product that is not a weight
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    def swiglu(x, gate, up, down):
        return act(jax.nn.silu(x @ gate) * (x @ up)) @ down

    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        h = h * embedding_m
        causal = pos[None, :] <= pos[:, None]
        gn = groups * d_state
        conv_dim = d_inner + 2 * gn
        mw, aw, lw = weights["mamba"], weights["attention"], weights["layers"]
        n_experts = lw["moe_gate"]["q"].shape[1]
        seen = {"mamba": 0, "attention": 0}
        shortfalls = []
        for l, kind in enumerate(layer_types):
            i = seen[kind]
            seen[kind] += 1
            x = _rms_norm(h, lw["attn_norm"][l], eps)
            if kind == "mamba":
                proj = act(x) @ _dequant(mw["ssm_in"], i)
                z = proj[:, :d_inner]
                xbc = proj[:, d_inner: d_inner + conv_dim]
                dt = proj[:, d_inner + conv_dim:]
                # depthwise causal conv: tap d_conv-1 on the current token,
                # zeros before the sequence
                padded = jnp.concatenate(
                    [jnp.zeros((d_conv - 1, conv_dim), jnp.float32), xbc])
                conv = mw["conv_b"][i] + sum(
                    padded[k: k + T] * mw["conv_w"][i][k]
                    for k in range(d_conv))
                xbc = act(jax.nn.silu(conv))
                xs = xbc[:, :d_inner].reshape(T, ssm_heads, ssm_head_dim)
                per_group = ssm_heads // groups
                b_h = jnp.repeat(xbc[:, d_inner: d_inner + gn].reshape(
                    T, groups, d_state), per_group, axis=1)
                c_h = jnp.repeat(xbc[:, d_inner + gn:].reshape(
                    T, groups, d_state), per_group, axis=1)
                delta = jax.nn.softplus(dt + mw["dt_bias"][i])   # [T, heads]
                a = -jnp.exp(mw["A_log"][i])

                def token(s, xs_t, a=a):
                    x_t, b_t, c_t, d_t = xs_t
                    s = (jnp.exp(d_t * a)[:, None, None] * s
                         + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
                    if lower == "state_bf16":
                        # reduce_precision, not a pair of converts: XLA may
                        # elide f32 -> bf16 -> f32 as excess precision
                        s = jax.lax.reduce_precision(s, exponent_bits=8,
                                                     mantissa_bits=7)
                    return s, jnp.einsum("hpn,hn->hp", s, c_t)

                s0 = jnp.zeros((ssm_heads, ssm_head_dim, d_state),
                               jnp.float32)
                _, y = jax.lax.scan(token, s0, (xs, b_h, c_h, delta))
                y = (y + mw["D"][i][None, :, None] * xs).reshape(T, d_inner)
                g = y * jax.nn.silu(z)                # gate first, one group
                g = g.reshape(T, groups, d_inner // groups)
                g = g * jax.lax.rsqrt(
                    jnp.mean(g * g, axis=-1, keepdims=True) + eps)
                g = g.reshape(T, d_inner) * mw["ssm_norm"][i]
                m = act(g) @ _dequant(mw["ssm_out"], i)
            else:
                xa = act(x)
                q = (xa @ _dequant(aw["wq"], i)).reshape(T, heads, head_dim)
                k = (xa @ _dequant(aw["wk"], i)).reshape(T, kv_heads, head_dim)
                v = (xa @ _dequant(aw["wv"], i)).reshape(T, kv_heads, head_dim)
                if lower == "kv_int8":
                    k, v = _int8_rows(k), _int8_rows(v)
                q, k, v = act(q), act(k), act(v)
                k = jnp.repeat(k, heads // kv_heads, axis=1)
                v = jnp.repeat(v, heads // kv_heads, axis=1)
                scores = jnp.einsum("ihd,jhd->hij", q, k) * attention_m
                probs = jax.nn.softmax(
                    jnp.where(causal[None], scores, -jnp.inf), axis=-1)
                attn = jnp.einsum("hij,jhd->ihd", act(probs), v).reshape(
                    T, heads * head_dim)
                m = act(attn) @ _dequant(aw["wo"], i)
            h = h + residual_m * m

            x = act(_rms_norm(h, lw["mlp_norm"][l], eps))
            logits = x @ lw["router"][l].astype(jnp.float32)      # [T, E]
            own_top, own = jax.lax.top_k(logits, top_k)
            chosen = own if choices is None else choices[l]
            picked = jnp.take_along_axis(logits, chosen, axis=1)
            shortfalls.append(own_top[:, -1] - picked.min(axis=1))
            gates = jnp.zeros_like(logits).at[
                jnp.arange(T)[:, None], chosen].set(
                    jax.nn.softmax(picked, axis=-1))               # [T, E]

            def expert(acc, e, l=l, x=x, gates=gates):
                gate, up, down = (_dequant(lw[n], l, e) for n in
                                  ("moe_gate", "moe_up", "moe_down"))
                g_e = jax.lax.dynamic_index_in_dim(gates, e, axis=1,
                                                   keepdims=True)
                return acc + g_e * swiglu(x, gate, up, down), None

            routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                                     jnp.arange(n_experts))
            shared = swiglu(x, _dequant(lw["shared_gate"], l),
                            _dequant(lw["shared_up"], l),
                            _dequant(lw["shared_down"], l))
            h = h + residual_m * (routed + shared)
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        head = emb["qe"].astype(jnp.float32) * emb["se"][:, None]  # [V, H]
        return (h @ head.T) / logits_div, jnp.stack(shortfalls)


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config, at
    a depth of ``layers`` (the first ``layers`` of ``layer_types``)."""
    heads = cfg["num_attention_heads"]
    return {
        "layer_types": tuple(cfg["layer_types"][:layers]), "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "eps": float(cfg["rms_norm_eps"]),
        "d_inner": cfg["mamba_expand"] * cfg["hidden_size"],
        "ssm_heads": cfg["mamba_n_heads"], "ssm_head_dim": cfg["mamba_d_head"],
        "d_state": cfg["mamba_d_state"], "groups": cfg["mamba_n_groups"],
        "d_conv": cfg["mamba_d_conv"],
        "top_k": int(cfg["num_experts_per_tok"]),
        "mults": (float(cfg["embedding_multiplier"]),
                  float(cfg["residual_multiplier"]),
                  float(cfg["attention_multiplier"]),
                  float(cfg["logits_scaling"])),
    }
