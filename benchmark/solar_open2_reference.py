"""The plain reference of the Solar-Open2 stack (``model_type: solar_open2``)
as one chip's share runs it: a forward pass over one whole sequence in
float32 ``jax.numpy``.

Written from the published ``config.json`` of ``upstage/Solar-Open2-250B``;
for KDA from the Kimi Linear report (arXiv 2510.26692) and
``fla/layers/kda.py`` of flash-linear-attention, for the router from the
``solar_open`` family's modelling code. RMSNorm (``rms_norm_eps``), pre-norm
residual, no bias. Layer ``l`` is a GQA layer where ``l`` is in
``gqa_layers``, else a KDA layer:

    h_0 = Embed(ids);   x = RMSNorm(h)
    KDA:  q, k, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v))
                    (depthwise, causal, 4 taps a channel, zeros before, no bias)
          a head (64 of d = 128):  q̂ = q / ‖q‖ · d^-1/2;  k̂ = k / ‖k‖
          g_t = −exp(A_log[head]) · softplus(x W_f1 W_f2 + dt_bias)   (a channel)
          β_t = 2 · sigmoid(x W_β)                                    (a head)
          S̃ = Diag(exp(g_t)) S_{t−1};  S_t = S̃ + β_t k̂_t (v_t − S̃ᵀ k̂_t)ᵀ
          o_t = S_tᵀ q̂_t
          m = W_o [ RMSNorm_head(o_t) ⊙ sigmoid(x W_g1 W_g2) ]
    GQA:  q, k, v = x W_q, x W_k, x W_v                      (NO rotary)
          m = W_o [ softmax(q kᵀ · d^-1/2 + causal) v ⊙ sigmoid(x W_gate) ]
    h ← h + m
    x′ = RMSNorm(h);  s = sigmoid(x′ W_r);  the K largest of s + b
    g_e = γ s_e / Σ_chosen s
    h ← h + W_sd(SiLU(W_sg x′) ⊙ W_su x′) + Σ_{e chosen, HELD} g_e W_d,e(SiLU(W_g,e x′) ⊙ W_u,e x′)
    logits = RMSNorm(h) W_head                                 (the held rows)

The recurrence is the recurrence itself, one token at a time under
``lax.scan``, no chunking and no WY form; attention is the formula; the
experts HELD are computed for every token, one at a time in a loop, and
combined by a gate that is zero off the chosen. What the experts held
elsewhere would add is left out, as in the program. No cache, no kernel, no
batching, no bfloat16: every matrix product at ``highest`` precision. It is
handed int8 tensors and their scales and dequantises them itself. It imports
nothing from the program.

What ``config.json`` alone does not settle (the configuration's ``assumed``
lists each): ‖·‖ is ``sqrt(Σ x² + 1e-6)`` (fla's ``l2norm``); the decay's
and the output gate's projections are low rank, rank = the head size
(``kda_use_full_proj`` false); the head norm comes before the gate; an expert
is SwiGLU; the attention gate is elementwise over all 8192 channels.

``choices`` [layers, T, K] hands the forward the experts to use in place of
its own K largest (the adapter passes the program's); the gates stay the
reference's own scores of them, and the forward also returns, per position,
how far the lowest ``s + b`` among them lies under the reference's own K-th
largest. ``lower`` is a control, the same forward with one thing kept one
precision below what the configuration states: ``"fp8"`` rounds the inputs of
every product that are not weights to float8 e4m3; ``"kv_int8"`` rounds K and
V of the GQA layers to int8 with one scale per token and kv head;
``"state_bf16"`` rounds the KDA state to bfloat16 after every token.
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


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=(
    "kinds", "heads", "kv_heads", "head_dim", "eps", "kda_heads", "kda_dim",
    "d_conv", "top_k", "gamma", "expert_offset", "neg_eigval", "lower",
    "expert_layer_only"))
def forward_logits(weights, ids, at, choices=None, *, kinds, heads, kv_heads,
                   head_dim, eps, kda_heads, kda_dim, d_conv, top_k, gamma,
                   expert_offset, neg_eigval, lower=None,
                   expert_layer_only=False):
    """(logits [len(at), V held], shortfall [layers, T]) of one sequence
    ``ids`` [T] at positions ``at``; ``kinds`` a string of ``A`` (GQA) and
    ``K`` (KDA), one a layer. ``expert_layer_only``: ``ids`` is instead a
    float32 ``x`` [T, hidden], and what comes back is layer 0's expert branch
    [T, hidden] (the share test adds the shares' up)."""
    if lower not in CONTROLS:
        raise ValueError(f"unknown control {lower!r}")

    def act(x):     # the input of a product that is not a weight
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    kw, aw, lw = weights["kda"], weights["attention"], weights["layers"]
    held = lw["moe_up"]["q"].shape[1]

    def swiglu(x, gate, up, down):
        return act(jax.nn.silu(x @ gate) * (x @ up)) @ down

    def experts(x, l, chosen_l):
        """Layer ``l``'s expert layer on normed ``x`` [T, H]."""
        T = x.shape[0]
        x = act(x)
        s = jax.nn.sigmoid(x @ lw["router"][l].astype(jnp.float32))
        biased = s + lw["router_bias"][l].astype(jnp.float32)     # [T, E]
        own_top, own = jax.lax.top_k(biased, top_k)
        chosen = own if chosen_l is None else chosen_l
        picked = jnp.take_along_axis(biased, chosen, axis=1)
        short = own_top[:, -1] - picked.min(axis=1)
        score = jnp.take_along_axis(s, chosen, axis=1)
        gates = jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(
            gamma * score / jnp.sum(score, axis=1, keepdims=True))

        def expert(acc, e):
            gate, up, down = (_dequant(lw[n], l, e) for n in
                              ("moe_gate", "moe_up", "moe_down"))
            g_e = jax.lax.dynamic_index_in_dim(gates, expert_offset + e,
                                               axis=1, keepdims=True)
            return acc + g_e * swiglu(x, gate, up, down), None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
        shared = swiglu(x, _dequant(lw["shared_gate"], l),
                        _dequant(lw["shared_up"], l),
                        _dequant(lw["shared_down"], l))
        return routed + shared, short

    def kda(x, i):
        """KDA layer ``i`` (among KDA layers) on normed ``x`` [T, H]."""
        T, width = x.shape[0], kda_heads * kda_dim
        xa = act(x)
        qkv = jnp.concatenate(
            [xa @ _dequant(kw[n], i) for n in ("wq", "wk", "wv")], axis=1)
        # depthwise causal conv: tap d_conv-1 on the current token, zeros
        # before the sequence, no bias
        padded = jnp.concatenate(
            [jnp.zeros((d_conv - 1, 3 * width), jnp.float32), qkv])
        conv = sum(padded[t: t + T] * kw["conv_w"][i][t]
                   for t in range(d_conv))
        q, k, v = (act(part).reshape(T, kda_heads, kda_dim) for part in
                   jnp.split(jax.nn.silu(conv), 3, axis=1))
        q, k = _unit(q) * kda_dim ** -0.5, _unit(k)
        rate = act(xa @ _dequant(kw["f_a"], i)) @ _dequant(kw["f_b"], i)
        g = -jnp.exp(kw["A_log"][i])[:, None] * jax.nn.softplus(
            rate + kw["dt_bias"][i]).reshape(T, kda_heads, kda_dim)
        beta = jax.nn.sigmoid(xa @ _dequant(kw["w_beta"], i))     # [T, heads]
        if neg_eigval:
            beta = 2.0 * beta

        def token(s, xs_t):
            q_t, k_t, v_t, g_t, b_t = xs_t
            s = jnp.exp(g_t)[:, :, None] * s                   # [heads, K, V]
            read = jnp.einsum("hkv,hk->hv", s, k_t)
            s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
            if lower == "state_bf16":
                # reduce_precision, not a pair of converts: XLA may elide
                # f32 -> bf16 -> f32 as excess precision
                s = jax.lax.reduce_precision(s, exponent_bits=8,
                                             mantissa_bits=7)
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        s0 = jnp.zeros((kda_heads, kda_dim, kda_dim), jnp.float32)
        _, o = jax.lax.scan(token, s0, (q, k, v, g, beta))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
            * kw["o_norm"][i]
        gate = jax.nn.sigmoid(
            act(xa @ _dequant(kw["g_a"], i)) @ _dequant(kw["g_b"], i))
        return act(o.reshape(T, width) * gate) @ _dequant(kw["wo"], i)

    def gqa(x, i, causal):
        T = x.shape[0]
        xa = act(x)
        q = (xa @ _dequant(aw["wq"], i)).reshape(T, heads, head_dim)
        k = (xa @ _dequant(aw["wk"], i)).reshape(T, kv_heads, head_dim)
        v = (xa @ _dequant(aw["wv"], i)).reshape(T, kv_heads, head_dim)
        if lower == "kv_int8":
            k, v = _int8_rows(k), _int8_rows(v)
        q, k, v = act(q), act(k), act(v)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("ihd,jhd->hij", q, k) * head_dim ** -0.5
        probs = jax.nn.softmax(
            jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("hij,jhd->ihd", act(probs), v).reshape(
            T, heads * head_dim)
        gate = jax.nn.sigmoid(xa @ _dequant(aw["w_gate"], i))
        return act(attn * gate) @ _dequant(aw["wo"], i)

    with jax.default_matmul_precision("highest"):
        if expert_layer_only:
            return experts(ids, 0, choices)
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        causal = pos[None, :] <= pos[:, None]
        seen = {"A": 0, "K": 0}
        shortfalls = []
        for l, kind in enumerate(kinds):
            i = seen[kind]
            seen[kind] += 1
            x = _rms_norm(h, lw["attn_norm"][l], eps)
            h = h + (kda(x, i) if kind == "K" else gqa(x, i, causal))
            m, short = experts(_rms_norm(h, lw["mlp_norm"][l], eps), l,
                               None if choices is None else choices[l])
            shortfalls.append(short)
            h = h + m
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        return h @ _dequant(weights["lm_head"]), jnp.stack(shortfalls)


def layer_kinds(cfg: dict, layers: int) -> str:
    """``A`` where the layer is in ``gqa_layers``, ``K`` elsewhere, for the
    first ``layers`` layers."""
    gqa = set(cfg["gqa_layers"])
    return "".join("A" if l in gqa else "K" for l in range(layers))


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config, at
    a depth of ``layers``."""
    linear = cfg["linear_attn_config"]
    return {
        "kinds": layer_kinds(cfg, layers),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "eps": float(cfg["rms_norm_eps"]),
        "kda_heads": linear["num_heads"], "kda_dim": linear["head_dim"],
        "d_conv": linear["short_conv_kernel_size"],
        "top_k": int(cfg["num_experts_per_tok"]),
        "gamma": float(cfg["routed_scaling_factor"]),
        "expert_offset": int(cfg["serving"].get("expert_offset", 0)),
        "neg_eigval": bool(cfg["kda_allow_neg_eigval"]),
    }
