"""The plain reference of the Nemotron-H stack (``model_type: nemotron_h``;
Nemotron-3-Super): a forward pass over one whole sequence in float32
``jax.numpy``.

Written from the published ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``. RMSNorm (``norm_eps``),
pre-norm residual, no bias but the conv's, ONE norm and ONE sub-layer a
layer. With ``kind = hybrid_override_pattern[l]``:

    h_0 = Embed(ids);   x = RMSNorm_l(h)
    M:  [z | xBC | dt] = x W_in        (d_inner | d_inner + 2 G N | heads)
        xBC = SiLU(conv1d_K(xBC) + b)  (depthwise, causal, zeros before)
        Δ = softplus(dt + dt_bias);  A = −exp(A_log)
        S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t B_{g,t}ᵀ;  y_t = S_t C_{g,t} + D x_t
        m = W_out · RMSNorm_groups(y ⊙ SiLU(z))    (gate first, G groups)
    *:  q, k, v = x W_q, x W_k, x W_v              (NO rotary)
        m = W_o · softmax(q kᵀ / √head_dim + causal) v
    E:  s = sigmoid(x W_g);  the K largest of s + b;  g_e = γ s_e / Σ_chosen s
        u = x W_ld                                  (hidden → latent)
        r = Σ_{e chosen, held} g_e · W2_e relu(W1_e u)²      (no gate matrix)
        m = r W_lu + Ws2 relu(Ws1 x)²               (latent → hidden; shared)
    h ← h + m
    logits = RMSNorm_f(h) W_head                    (untied; the rows held)

**The share.** The weights tree holds the experts ``expert_offset ..
expert_offset + held − 1`` of the router's ``n_routed`` and a slice of the
vocabulary. The router scores all ``n_routed``, the gates are normalised over
all the K chosen, the sum runs over the chosen experts that are held, and
what the others would add is left out, as in the program: the reference is
given the same share.

The recurrence is the recurrence itself, one token at a time under
``lax.scan``, no chunking; attention is the formula; every HELD expert is
computed for every token, one expert at a time in a loop (one expert's two
matrices dequantised at a time), and combined by a gate that is zero off the
chosen. No cache, no kernel, no batching, no bfloat16: every matrix product
at ``highest`` precision. It is handed int8 tensors and their scales and
dequantises them itself. It imports nothing from the program.

``choices`` [E layers, T, K] hands the forward the experts to use in place of
its own K largest (the adapter passes the program's:
``adapters/nemotron_h.py`` says why); the gates stay the reference's own
scores of them, and the forward also returns, per position, how far the
lowest ``s + b`` of them lies under the reference's own K-th largest.
``lower`` is a control, the same forward with one thing kept one precision
below what the configuration states: ``"fp8"`` rounds the inputs of every
product that are not weights (activations, q, K, V, attention weights, the
mixer's x, B, C, the latent rows) to float8 e4m3; ``"kv_int8"`` rounds K and V
to int8 with one scale per token and kv head; ``"state_bf16"`` rounds the
recurrent state to bfloat16 after every token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CONTROLS = (None, "fp8", "kv_int8", "state_bf16")
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


@functools.partial(jax.jit, static_argnames=(
    "pattern", "heads", "kv_heads", "head_dim", "eps", "d_inner", "ssm_heads",
    "ssm_head_dim", "d_state", "groups", "d_conv", "top_k", "gamma",
    "expert_offset", "lower", "expert_layer_only"))
def forward_logits(weights, ids, at, choices=None, *, pattern, heads,
                   kv_heads, head_dim, eps, d_inner, ssm_heads, ssm_head_dim,
                   d_state, groups, d_conv, top_k, gamma, expert_offset,
                   lower=None, expert_layer_only=False):
    """(logits [len(at), V held], shortfall [E layers, T]) of one sequence
    ``ids`` [T] at positions ``at``. ``expert_layer_only``: ``ids`` is
    instead a float32 ``x`` [T, hidden], and what comes back is expert layer
    0's branch ``m`` [T, hidden] (the share test adds the shares' up)."""
    if lower not in CONTROLS:
        raise ValueError(f"unknown control {lower!r}")

    def act(x):     # the input of a product that is not a weight
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    mw, aw, ew = weights["mamba"], weights["attention"], weights["moe"]
    held = ew["moe_up"]["q"].shape[1]

    def experts(x, i, chosen_i):
        """Expert layer ``i`` (among E layers) on normed ``x`` [T, H]."""
        T = x.shape[0]
        s = jax.nn.sigmoid(x @ ew["router"][i].astype(jnp.float32))
        biased = s + ew["router_bias"][i].astype(jnp.float32)     # [T, E]
        own_top, own = jax.lax.top_k(biased, top_k)
        chosen = own if chosen_i is None else chosen_i
        picked = jnp.take_along_axis(biased, chosen, axis=1)
        short = own_top[:, -1] - picked.min(axis=1)
        score = jnp.take_along_axis(s, chosen, axis=1)
        gates = jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(
            gamma * score / jnp.sum(score, axis=1, keepdims=True))
        u = act(act(x) @ _dequant(ew["latent_down"], i))          # [T, W]

        def expert(acc, e):
            up, down = _dequant(ew["moe_up"], i, e), \
                _dequant(ew["moe_down"], i, e)
            g_e = jax.lax.dynamic_index_in_dim(gates, expert_offset + e,
                                               axis=1, keepdims=True)
            return acc + g_e * (act(_relu2(u @ up)) @ down), None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(u), jnp.arange(held))
        shared = act(_relu2(act(x) @ _dequant(ew["shared_up"], i))) \
            @ _dequant(ew["shared_down"], i)
        return act(routed) @ _dequant(ew["latent_up"], i) + shared, short

    with jax.default_matmul_precision("highest"):
        if expert_layer_only:
            return experts(ids, 0, choices)
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        causal = pos[None, :] <= pos[:, None]
        gn = groups * d_state
        conv_dim = d_inner + 2 * gn
        seen = dict.fromkeys(KINDS, 0)
        shortfalls = []
        for l, kind in enumerate(pattern):
            i = seen[kind]
            seen[kind] += 1
            x = _rms_norm(h, weights["layers"]["norm"][l], eps)
            if kind == "M":
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
                per_group = ssm_heads // groups     # heads g*per .. share B, C
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
                g = y * jax.nn.silu(z)              # gate first, then groups
                g = g.reshape(T, groups, d_inner // groups)
                g = g * jax.lax.rsqrt(
                    jnp.mean(g * g, axis=-1, keepdims=True) + eps)
                g = g.reshape(T, d_inner) * mw["ssm_norm"][i]
                m = act(g) @ _dequant(mw["ssm_out"], i)
            elif kind == "*":
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
                m = act(attn) @ _dequant(aw["wo"], i)
            else:
                m, short = experts(
                    x, i, None if choices is None else choices[i])
                shortfalls.append(short)
            h = h + m
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        return h @ _dequant(weights["lm_head"]), jnp.stack(shortfalls)


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config, at
    a depth of ``layers`` (the first ``layers`` characters of
    ``hybrid_override_pattern``)."""
    return {
        "pattern": cfg["hybrid_override_pattern"][:layers],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "eps": float(cfg["norm_eps"]),
        "d_inner": cfg["mamba_num_heads"] * cfg["mamba_head_dim"],
        "ssm_heads": cfg["mamba_num_heads"],
        "ssm_head_dim": cfg["mamba_head_dim"],
        "d_state": cfg["ssm_state_size"], "groups": cfg["n_groups"],
        "d_conv": cfg["conv_kernel"],
        "top_k": int(cfg["num_experts_per_tok"]),
        "gamma": float(cfg["routed_scaling_factor"]),
        "expert_offset": int(cfg["serving"].get("expert_offset", 0)),
    }
