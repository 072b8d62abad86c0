"""The plain reference of the Laguna block (``model_type: laguna``;
Laguna-S-2.1): a forward pass over a whole sequence in float32 ``jax.numpy``.

Written from the published ``config.json`` and the conventions of the family
whose key names it uses; what the config does not settle is listed in the
configuration's ``assumed``. Layer ``l`` is FULL where ``layer_types[l]`` says
``full_attention`` and has ``num_attention_heads_per_layer[l]`` query heads
``H_l`` over ``num_key_value_heads`` kv heads of ``head_dim`` ``D``:

    h = E[ids]
    x = RMSNorm(h; g1_l);  q = x Wq_l [T, H_l, D];  k, v = x Wk_l, x Wv_l [T, Hkv, D]
    full:    q, k rotated on their FIRST D x partial_rotary_factor numbers
             (rotate-half pairs inside that part) under YaRN's inverse
             frequencies over that part, cos and sin times attention_factor
    window:  q, k rotated on all D numbers, plain theta, no scaling
    a_j = softmax_s(q_j . k_s / sqrt(D)) v,  s <= t;  window: t - sliding_window < s
          query head j reads kv head j // (H_l / Hkv)
    g = sigmoid(x Wg_l) [T, H_l];  h <- h + concat_j(g_j a_j) Wo_l
    x = RMSNorm(h; g2_l)
    l in mlp_only_layers:  h <- h + Wd (silu(Wg' x) * (Wu x))
    else:  s = softmax(x Wr_l) over all experts, float32; the K largest;
           gates = s_chosen / sum(s_chosen) x moe_routed_scaling_factor
           h <- h + Shared(x) + sum_{e chosen and held} gate_e Expert_e(x)
    logits = RMSNorm(h; g_final) W_head

**The share**, ``choices`` and the shortfall are ``kimi_k2_reference.py``'s:
the tree holds the experts ``expert_offset ..`` of the router's width and a
slice of the vocabulary; the gates are normalised over all K chosen and the
sum runs over the chosen that are held. The shortfall is read in the router's
LOGITS (a softmax over 256 puts its scores near 1/256, where a difference
says little): the reference's own K-th largest logit less the lowest among
the experts handed in.

No cache, no pages, no kernel, no bfloat16, one sequence at a time, every
matrix product at ``highest`` precision, the held experts one at a time (a
scan). It imports nothing from the program. ``lower`` turns it into a
control: ``"fp8"`` rounds the inputs of every matrix product that are not
weights to float8 e4m3; ``"kv_int8"`` rounds K (after the rotation) and V to
int8 with one scale per token and kv head; and three of this architecture's
own, each the forward with one of its mechanisms left out: ``"no_window"``
(the window layers attend over everything), ``"one_rope"`` (the window layers
rotated with the full layers' tables) and ``"no_head_gate"`` (no gate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kimi_k2_reference import _rope, yarn_inv_freq
from .reference import _dequant, _int8_rows, _rms_norm

CONTROLS = ("fp8", "kv_int8", "no_window", "one_rope", "no_head_gate")


def _leaf(w: dict, *index) -> jnp.ndarray:
    return _dequant({"q": w["q"][index], "s": w["s"][index]})


def _rotated(x, pos, inv_freq, scale):
    """``x`` [T, heads, D] with its leading ``2 len(inv_freq)`` numbers
    rotated and the rest as they are."""
    part = 2 * len(inv_freq)
    return jnp.concatenate(
        [_rope(x[..., :part], pos, inv_freq, scale), x[..., part:]], -1)


@functools.partial(jax.jit, static_argnames=(
    "full", "heads", "dense", "kv_heads", "head_dim", "eps", "window",
    "full_rope", "window_theta", "top_k", "gamma", "expert_offset", "lower"))
def forward_logits(weights, ids, at, choices=None, *, full, heads, dense,
                   kv_heads, head_dim, eps, window, full_rope, window_theta,
                   top_k, gamma, expert_offset, lower=None):
    """(logits [len(at), V held], shortfall [expert layers, T]) of one
    sequence ``ids`` [T] at positions ``at``. ``full``, ``heads`` and
    ``dense`` name every layer's kind, query heads and whether its MLP is
    dense; ``full_rope`` = (theta, partial factor, YaRN factor, original
    positions, beta_fast, beta_slow, attention factor)."""
    if lower not in (None, *CONTROLS):
        raise ValueError(f"unknown control {lower!r}")

    def act(x):     # the input of a matrix product
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    theta, partial, factor, original, fast, slow, attention_factor = full_rope
    rotary = int(head_dim * partial)
    full_freq = (yarn_inv_freq(rotary, theta, factor, original, fast, slow)
                 if factor > 1 else
                 1.0 / theta ** (jnp.arange(0, rotary, 2) / rotary))
    rope = {True: (full_freq, attention_factor if factor > 1 else 1.0),
            False: (1.0 / window_theta ** (
                jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim),
                    1.0)}
    if lower == "one_rope":
        rope[False] = rope[True]

    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        causal = pos[None, :] <= pos[:, None]
        near = pos[:, None] - pos[None, :] < window

        def attention(aw, i, kind, H, x):
            q = (x @ _leaf(aw["wq"], i)).reshape(T, H, head_dim)
            k = (x @ _leaf(aw["wk"], i)).reshape(T, kv_heads, head_dim)
            v = (x @ _leaf(aw["wv"], i)).reshape(T, kv_heads, head_dim)
            q, k = (_rotated(t, pos, *rope[kind]) for t in (q, k))
            if lower == "kv_int8":
                k, v = _int8_rows(k), _int8_rows(v)
            q, k, v = act(q), act(k), act(v)
            k = jnp.repeat(k, H // kv_heads, axis=1)
            v = jnp.repeat(v, H // kv_heads, axis=1)
            scores = jnp.einsum("ihd,jhd->hij", q, k) / head_dim ** 0.5
            seen = causal if kind or lower == "no_window" else causal & near
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
            a = jnp.einsum("hij,jhd->ihd", act(probs), v)       # [T, H, D]
            if "w_gate" in aw and lower != "no_head_gate":
                a = a * jax.nn.sigmoid(x @ _leaf(aw["w_gate"], i))[:, :, None]
            return act(a.reshape(T, -1)) @ _leaf(aw["wo"], i)

        def swiglu(x, gate, up, down):
            return act(jax.nn.silu(x @ gate) * (x @ up)) @ down

        dw, lw = weights["dense"], weights.get("layers")
        seen_kind = {True: 0, False: 0}
        n_dense = n_moe = 0
        shortfalls = []
        for l, (kind, H, is_dense) in enumerate(zip(full, heads, dense)):
            nw = dw if is_dense else lw
            n = n_dense if is_dense else n_moe
            x = act(_rms_norm(h, nw["attn_norm"][n], eps))
            h = h + attention(weights["full" if kind else "window"],
                              seen_kind[kind], kind, H, x)
            seen_kind[kind] += 1
            x = act(_rms_norm(h, nw["mlp_norm"][n], eps))
            if is_dense:
                h = h + swiglu(x, _leaf(dw["gate"], n), _leaf(dw["up"], n),
                               _leaf(dw["down"], n))
                n_dense += 1
                continue
            logits = x @ lw["router"][n].astype(jnp.float32)
            own_top, own = jax.lax.top_k(logits, top_k)
            chosen = own if choices is None else choices[n]
            z_top = jnp.take_along_axis(logits, chosen, axis=1)
            shortfalls.append(own_top[:, -1] - z_top.min(axis=1))
            s_top = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                        chosen, axis=1)
            gates = jnp.zeros_like(logits).at[
                jnp.arange(T)[:, None], chosen].set(
                    gamma * s_top / s_top.sum(axis=1, keepdims=True))
            held = lw["moe_gate"]["q"].shape[1]

            def expert(acc, e, n=n, x=x, gates=gates):
                g, u, d = (_dequant({"q": lw[name]["q"][n][e],
                                     "s": lw[name]["s"][n][e]})
                           for name in ("moe_gate", "moe_up", "moe_down"))
                gate = jax.lax.dynamic_index_in_dim(
                    gates, expert_offset + e, axis=1, keepdims=True)
                return acc + gate * swiglu(x, g, u, d), None

            routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                                     jnp.arange(held))
            h = h + routed + swiglu(x, _leaf(lw["shared_gate"], n),
                                    _leaf(lw["shared_up"], n),
                                    _leaf(lw["shared_down"], n))
            n_moe += 1
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        short = (jnp.stack(shortfalls) if shortfalls
                 else jnp.zeros((0, T), jnp.float32))
        return h @ _dequant(weights["lm_head"]), short


def layer_kinds(cfg: dict, layers: int) -> tuple[tuple, tuple, tuple]:
    """(full, query heads, dense MLP) of the first ``layers`` layers, from
    the published per-layer lists."""
    return (tuple(t == "full_attention" for t in cfg["layer_types"][:layers]),
            tuple(int(n) for n in
                  cfg["num_attention_heads_per_layer"][:layers]),
            tuple(l in cfg["mlp_only_layers"] for l in range(layers)))


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config,
    at a depth of ``layers``."""
    full, heads, dense = layer_kinds(cfg, layers)
    ropes = cfg["rope_parameters"]
    f, w = ropes["full_attention"], ropes["sliding_attention"]
    yarn = f.get("rope_type") == "yarn"
    return {"full": full, "heads": heads, "dense": dense,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "eps": float(cfg["rms_norm_eps"]),
            "window": int(cfg["sliding_window"]),
            "full_rope": (float(f["rope_theta"]),
                          float(f.get("partial_rotary_factor", 1.0)),
                          float(f["factor"]) if yarn else 1.0,
                          int(f.get("original_max_position_embeddings", 0)),
                          float(f.get("beta_fast", 32.0)),
                          float(f.get("beta_slow", 1.0)),
                          float(f.get("attention_factor", 1.0))),
            "window_theta": float(w["rope_theta"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "gamma": float(cfg["moe_routed_scaling_factor"]),
            "expert_offset": int(cfg["serving"].get("expert_offset", 0))}
