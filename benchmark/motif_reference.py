"""The plain reference of the Motif block (``model_type: Motif``;
Motif-3-Beta): a forward pass over a whole sequence in float32 ``jax.numpy``.

Written from the published ``config.json`` and the papers its keys name (MLA:
arXiv:2405.04434; grouped differential attention: arXiv:2510.06949 and
Differential Transformer V2; mHC: arXiv:2512.24880; PolyNorm:
arXiv:2411.03884; the output gate: arXiv:2505.06708); what none of them
states is listed in the configuration's ``assumed``. A token's residual state
is ``X [n, C]``, ``n = mhc_expansion_rate`` streams. Around every sub-layer F
(attention, then feed-forward):

    x~ = RMSNorm(vec(X));  z = x~ phi                      (n C numbers)
    H_pre = sigmoid(a0 z[:n] + b[:n]);  H_post = 2 sigmoid(a1 z[n:2n] + b[n:2n])
    H_res = Sinkhorn(exp(a2 mat(z[2n:]) + mat(b[2n:])))    mhc_sinkhorn_iters
            alternations: rows to sum 1, then columns
    u = H_pre X;  y = clamp(F(RMSNorm(u)), +-hidden_clamp);  X <- H_res X + H_post^T y

``X_0`` is n copies of the embedding; after the last layer ``h = sum_i X[i]``,
the final RMSNorm and the untied head over the vocabulary rows held.

Attention (GDLA), heads in the PUBLISHED order (64 signal heads, then the 16
noise heads); group ``g`` of the 16 latent kv groups serves signal heads
``4g..4g+3`` and noise head ``64 + g``:

    c_q = RMSNorm(x W_dq);  q_h = c_q W_uq,h = [q_nope_h (128) | q_rope_h (64)]
    [c_kv (512) | k_r (64)] = x W_dkv;  c = RMSNorm(c_kv);  RoPE(theta) on
        q_rope and k_r, rotate-half, NO YaRN (apply_yarn_scaling false)
    [k_nope_g (128) | v_g (128)] = c W_ukv,g            for EVERY position
    A_h(t) = sum_{s visible} softmax_s((q_nope_h.k_nope_g(s) + q_rope_h.k_r(s)) sigma) v_g(s)
        visible: s <= t, and t - s < sliding_window where (layer + 1) %
        sliding_window_period != 0;  sigma = (128 + 64)^-1/2
    o_h = A_h - sigmoid(x w_lam,h) A_noise(g(h))          h < 64
    F = (concat_h o_h * sigmoid(x W_gate)) W_o

Feed-forward: ``PolyNorm(z) = s (a1 z/rms(z) + a2 z^2/rms(z^2) + a3
z^3/rms(z^3)) + clamp(b, +-polynorm_bias_clamp)``, rms over the unit's whole
row. The first ``n_dense_first_layers`` layers: ``W_down(PolyNorm(x W_gate) *
(x W_up))``. The others: ``s = sigmoid(x W_r)`` in float32, the K largest,
``g_e = route_scale s_e / sum_chosen s``, ``F = unit_shared(x) + sum_{e chosen
and held} g_e unit_e(x)``, the routed experts of a layer under ONE PolyNorm
(coefficient row 0), the shared expert under its own (row 1).

**The share**, ``choices`` and the shortfall are ``kimi_k2_reference.py``'s:
the tree holds the experts ``expert_offset ..`` of the router's width and a
slice of the vocabulary; the gates are normalised over all K chosen and the
sum runs over the chosen that are held.

No cache, no pages, no kernel, no bfloat16, attention NOT absorbed (K and V
expanded for every position, the window a mask), one sequence at a time,
every matrix product at ``highest`` precision, the held experts one at a time
(a scan, so the forward fits the chip). It imports nothing from the program.
``lower="fp8"`` rounds the inputs of every matrix product that are not
weights to float8 e4m3. ``lam_scale`` multiplies the differential gate (1; a
test turns the subtraction off with 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import _dequant, _rms_norm, _rope


def _leaf(w: dict, *index) -> jnp.ndarray:
    return _dequant({"q": w["q"][index], "s": w["s"][index]})


def sinkhorn(m: jnp.ndarray, iters: int) -> jnp.ndarray:
    """``iters`` alternations over the positive ``m`` [T, n, n]: every row to
    sum 1, then every column."""
    for _ in range(iters):
        m = m / m.sum(axis=-1, keepdims=True)
        m = m / m.sum(axis=-2, keepdims=True)
    return m


def poly_norm(z, coef, bias, scale: float, clamp: float, eps: float):
    def normed(p):
        return p * jax.lax.rsqrt(jnp.mean(p * p, axis=-1, keepdims=True) + eps)

    return scale * (coef[0] * normed(z) + coef[1] * normed(z ** 2)
                    + coef[2] * normed(z ** 3)) + jnp.clip(bias, -clamp, clamp)


@functools.partial(jax.jit, static_argnames=(
    "dense_layers", "moe_layers", "heads", "noise", "groups", "nope", "rope",
    "v_dim", "rank", "eps", "theta", "window", "period", "streams", "iters",
    "poly_scale", "poly_clamp", "hidden_clamp", "top_k", "gamma",
    "expert_offset", "lower", "lam_scale"))
def forward_logits(weights, ids, at, choices=None, *, dense_layers,
                   moe_layers, heads, noise, groups, nope, rope, v_dim, rank,
                   eps, theta, window, period, streams, iters, poly_scale,
                   poly_clamp, hidden_clamp, top_k, gamma, expert_offset,
                   lower=None, lam_scale=1.0):
    """(logits [len(at), V held], shortfall [moe_layers, T]) of one sequence
    ``ids`` [T] at positions ``at``."""
    if lower not in (None, "fp8"):
        raise ValueError(f"unknown control {lower!r}")

    def act(x):     # the input of a matrix product
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    signal = heads - noise
    per = signal // groups
    sigma = (nope + rope) ** -0.5
    n = streams

    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        X = jnp.broadcast_to(h[:, None, :], (T, n, h.shape[1]))
        causal = pos[None, :] <= pos[:, None]
        near = pos[:, None] - pos[None, :] < window

        def poly(lw, l, unit, z):
            return poly_norm(z, lw["poly_coef"][l, unit],
                             lw["poly_bias"][l, unit], poly_scale, poly_clamp,
                             eps)

        def connected(lw, l, sub, X, norm, f):
            x = X.reshape(T, -1)
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps) * lw["mhc_norm"][l, sub]
            z = x @ lw["mhc_phi"][l, sub]
            a, b = lw["mhc_alpha"][l, sub], lw["mhc_bias"][l, sub]
            pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
            post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
            res = sinkhorn(jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]
                                   ).reshape(T, n, n), iters)
            u = jnp.einsum("ts,tsc->tc", pre, X)
            y = f(act(_rms_norm(u, norm[l], eps)))
            if hidden_clamp:
                y = jnp.clip(y, -hidden_clamp, hidden_clamp)
            return (jnp.einsum("tsu,tuc->tsc", res, X)
                    + post[:, :, None] * y[:, None, :])

        def attention(lw, l, layer, x):
            c_q = act(_rms_norm(x @ _leaf(lw["wq_a"], l), lw["q_a_norm"][l],
                                eps))
            q = (c_q @ _leaf(lw["wq_b"], l)).reshape(T, heads, nope + rope)
            ckv = x @ _leaf(lw["wkv_a"], l)
            c = _rms_norm(ckv[:, :rank], lw["kv_a_norm"][l], eps)
            k_r = _rope(ckv[:, None, rank:], pos, theta)
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], pos, theta)], -1)
            kv = (act(c) @ _leaf(lw["wkv_b"], l)).reshape(
                T, groups, nope + v_dim)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_r, (T, groups, rope))], -1)
            # every query head beside its group's keys and values
            group_of = jnp.concatenate([jnp.arange(signal) // per,
                                        jnp.arange(noise)])
            scores = jnp.einsum("ihd,jhd->hij", act(q),
                                act(k[:, group_of])) * sigma
            seen = causal if (layer + 1) % period == 0 else causal & near
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
            A = jnp.einsum("hij,jhd->ihd", act(probs),
                           act(kv[:, group_of, nope:]))      # [T, heads, v]
            lam = lam_scale * jax.nn.sigmoid(x @ _leaf(lw["w_lam"], l))
            o = A[:, :signal] - lam[:, :, None] * jnp.repeat(
                A[:, signal:], per, axis=1)
            o = o.reshape(T, -1) * jax.nn.sigmoid(x @ _leaf(lw["w_gate"], l))
            return act(o) @ _leaf(lw["wo"], l)

        def unit(lw, l, which, x, gate, up, down):
            return act(poly(lw, l, which, x @ gate) * (x @ up)) @ down

        dw = weights["dense"]
        for l in range(dense_layers):
            X = connected(dw, l, 0, X, dw["attn_norm"],
                          lambda x, l=l: attention(dw, l, l, x))
            X = connected(dw, l, 1, X, dw["mlp_norm"],
                          lambda x, l=l: unit(
                              dw, l, 0, x, _leaf(dw["gate"], l),
                              _leaf(dw["up"], l), _leaf(dw["down"], l)))

        lw = weights["layers"]
        held = lw["moe_gate"]["q"].shape[1]
        shortfalls = []
        for l in range(moe_layers):
            X = connected(lw, l, 0, X, lw["attn_norm"],
                          lambda x, l=l: attention(lw, l, dense_layers + l, x))

            def feed_forward(x, l=l):
                s = jax.nn.sigmoid(x @ lw["router"][l].astype(jnp.float32))
                own_top, own = jax.lax.top_k(s, top_k)
                chosen = own if choices is None else choices[l]
                s_top = jnp.take_along_axis(s, chosen, axis=1)
                shortfalls.append(own_top[:, -1] - s_top.min(axis=1))
                gates = jnp.zeros_like(s).at[
                    jnp.arange(T)[:, None], chosen].set(
                        gamma * s_top / s_top.sum(axis=1, keepdims=True))

                def expert(acc, e):
                    g, u, d = (_dequant({"q": lw[name]["q"][l][e],
                                         "s": lw[name]["s"][l][e]})
                               for name in ("moe_gate", "moe_up", "moe_down"))
                    gate = jax.lax.dynamic_index_in_dim(
                        gates, expert_offset + e, axis=1, keepdims=True)
                    return acc + gate * unit(lw, l, 0, x, g, u, d), None

                routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                                         jnp.arange(held))
                return routed + unit(
                    lw, l, 1, x, _leaf(lw["shared_gate"], l),
                    _leaf(lw["shared_up"], l), _leaf(lw["shared_down"], l))

            X = connected(lw, l, 1, X, lw["mlp_norm"], feed_forward)
        h = act(_rms_norm(X.sum(axis=1)[at], weights["final_norm"], eps))
        short = (jnp.stack(shortfalls) if shortfalls
                 else jnp.zeros((0, T), jnp.float32))
        return h @ _dequant(weights["lm_head"]), short


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config,
    at a depth of ``layers`` (the leading dense layers first)."""
    dense = min(int(cfg["n_dense_first_layers"]), layers)
    return {"dense_layers": dense, "moe_layers": layers - dense,
            "heads": cfg["num_attention_heads"],
            "noise": cfg["num_noise_heads"],
            "groups": cfg["num_key_value_heads"],
            "nope": cfg["head_dim"] - cfg["qk_rope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
            "rank": cfg["kv_lora_rank"], "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "window": int(cfg["sliding_window"]),
            "period": int(cfg["sliding_window_period"]),
            "streams": int(cfg["mhc_expansion_rate"]),
            "iters": int(cfg["mhc_sinkhorn_iters"]),
            "poly_scale": float(cfg["polynorm_output_scale"]),
            "poly_clamp": float(cfg["polynorm_bias_clamp"]),
            "hidden_clamp": float(cfg["hidden_clamp"]),
            "top_k": int(cfg["experts_top_k"]),
            "gamma": float(cfg["route_scale"]),
            "expert_offset": int(cfg["serving"].get("expert_offset", 0))}
