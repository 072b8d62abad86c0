"""The plain reference of the GLM-5 block (``model_type: glm_moe_dsa``): a
forward pass over a whole sequence in float32 ``jax.numpy``.

Written from the published ``config.json`` and the family's published indexer
(DeepSeek-V3.2-Exp's ``Indexer``); what neither states is listed in the
configuration's ``assumed``. Pre-norm residual, RMSNorm, no bias anywhere but
the index key's LayerNorm. Per layer:

    x   = RMSNorm(h)
    c_q = RMSNorm(x W_dq);  q_j = c_q W_uq,j = [q_nope_j (192) | q_rope_j (64)]
    [c_kv (512) | k_r (64)] = x W_dkv;  c = RMSNorm(c_kv)
    k_r <- RoPE(k_r), ONE head shared by all;  q_rope_j <- RoPE(q_rope_j)
    [k_nope_j (192) | v_j (256)] = c W_ukv,j            for EVERY position
    -- the indexer --
    qI_i = c_q W_qI,i [128], i < 32;   kI = LayerNorm(x W_kI) [128], eps 1e-6
    the FIRST 64 numbers of qI_i and of kI rotated (the same frequencies)
    w    = (x W_w) [32] * 32^-1/2 * 128^-1/2
    I(t, s) = sum_i w_i(t) relu(qI_i(t) . kI(s)),  s <= t
    S_t  = the 2048 keys s <= t of largest I(t, s) (every s <= t while
           t + 1 <= 2048)
    -- attention over S_t only --
    score_j(t, s) = (q_nope_j(t).k_nope_j(s) + q_rope_j(t).k_r(s)) 256^-1/2
    h <- h + concat_j(sum_{s in S_t} softmax_s(score_j) v_j(s)) W_o

    the first ``first_k_dense_replace`` layers:  h <- h + SwiGLU(x'), x' = RMSNorm(h)
    the others:  s = sigmoid(x' W_g) in float32;  the K experts with the
        largest s + b;  g_e = gamma s_e / sum_{chosen} s
        h <- h + SwiGLU_shared(x') + sum_{e chosen and held} g_e SwiGLU_e(x')

then the final RMSNorm and the untied head over the vocabulary rows held.
RoPE is plain (``theta^(-2i/64)``, no scaling), rotate-half inside the
rotated part.

**The share** is kimi_k2's: the weights tree holds the experts
``expert_offset .. expert_offset + held - 1`` of the router's ``n_routed``
and a slice of the vocabulary; the gates are normalised over all K chosen,
the sum runs over the chosen experts that are held.

No cache, no kernel, no batching, no bfloat16, and attention is NOT absorbed:
K and V are expanded from the compressed row for every position. One
sequence at a time, ``block`` queries at a time against every position (a
row of 4.4k fits a chip that way), every matrix product at ``highest``
precision. It is handed int8 tensors and their scales and dequantises them
itself. It imports nothing from the program.

``choices`` [expert layers, T, K] hands the forward the experts to use, as
kimi_k2's does. ``selected`` [layers, T, index_topk] hands it the keys each
query attends (positions, -1 past the count) in place of its own
``index_topk`` largest (the adapter passes the program's:
``adapters/glm_dsa.py`` says why). The forward also returns, per layer and
position, how far the lowest-scored chosen key lies under the reference's own
``index_topk``-th largest ``I(t, s)`` (0 while the query sees no more than
that many; infinite for a key ``s > t``), and how far the COUNT of keys
chosen, and of distinct visible keys among them, is from ``min(t + 1,
index_topk)``.

``lower`` is a control, the same forward with one thing kept below what the
configuration states: ``"fp8"`` and ``"latent_int8"`` as kimi_k2's;
``"no_select"`` attends every key ``s <= t``; ``"no_relu"`` scores without
the relu; ``"index_unweighted"`` sums the heads with ``w_i = 1``; the last
two choose their own top-k. ``index_layer_shift`` scores layer ``l``'s
queries against layer ``l - shift``'s index keys (0; a test reads another
layer's ``kI`` with it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import _dequant, _int8_rows, _rms_norm

CONTROLS = ("fp8", "latent_int8", "no_select", "no_relu", "index_unweighted")
_NEG = -1e30


def _rope(x, positions, theta):
    """x [T, heads, D] rotated whole; rotate-half convention (the first half
    pairs with the second)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rotated * sin


def _rope_first(x, positions, theta, part: int):
    """The first ``part`` numbers of a head rotated, the rest as they are."""
    return jnp.concatenate(
        [_rope(x[..., :part], positions, theta), x[..., part:]], -1)


def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32) + bias.astype(jnp.float32))


def _leaf(w: dict, *index) -> jnp.ndarray:
    return _dequant({"q": w["q"][index], "s": w["s"][index]})


@functools.partial(jax.jit, static_argnames=(
    "dense_layers", "moe_layers", "heads", "nope", "rope", "v_dim", "rank",
    "eps", "theta", "top_k", "gamma", "expert_offset", "index_heads",
    "index_dim", "index_topk", "lower", "block", "index_layer_shift"))
def forward_logits(weights, ids, at, choices=None, selected=None, *,
                   dense_layers, moe_layers, heads, nope, rope, v_dim, rank,
                   eps, theta, top_k, gamma, expert_offset, index_heads,
                   index_dim, index_topk, lower=None, block=128,
                   index_layer_shift=0):
    """(logits [len(at), V held], expert shortfall [moe_layers, T], selection
    shortfall [layers, T], miscount [layers, T]) of one sequence ``ids``
    [T], ``T`` whole blocks of ``block`` queries, at positions ``at``."""
    if lower not in (None, *CONTROLS):
        raise ValueError(f"unknown control {lower!r}")
    T = ids.shape[0]
    block = min(block, T)
    if T % block:
        raise ValueError(f"{T} positions are not whole blocks of {block}")

    def act(x):     # the input of a matrix product
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    sigma = (nope + rope) ** -0.5
    k_sel = min(index_topk, T)

    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        blocks = pos.reshape(-1, block)
        index_keys = []          # every layer's kI, for ``index_layer_shift``

        def attention(lw, l, h, sel):
            """``sel`` [T, index_topk]: the keys to attend, or None."""
            x = act(_rms_norm(h, lw["attn_norm"][l], eps))
            c_q = act(_rms_norm(x @ _leaf(lw["wq_a"], l), lw["q_a_norm"][l],
                                eps))
            q = (c_q @ _leaf(lw["wq_b"], l)).reshape(T, heads, nope + rope)
            ckv = x @ _leaf(lw["wkv_a"], l)
            c = _rms_norm(ckv[:, :rank], lw["kv_a_norm"][l], eps)
            k_r = _rope(ckv[:, None, rank:], pos, theta)
            q_rope = _rope(q[..., nope:], pos, theta)
            if lower == "latent_int8":
                c, k_r = _int8_rows(c), _int8_rows(k_r)
            kv = (act(c) @ _leaf(lw["wkv_b"], l)).reshape(
                T, heads, nope + v_dim)
            k = act(jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_r, (T, heads, rope))],
                -1))
            v = act(kv[..., nope:])
            qq = act(jnp.concatenate([q[..., :nope], q_rope], -1))
            # the indexer
            qi = _rope_first((c_q @ _leaf(lw["index_wq"], l)).reshape(
                T, index_heads, index_dim), pos, theta, rope)
            ki = _rope_first(_layer_norm(
                x @ _leaf(lw["index_wk"], l), lw["index_k_norm"][l],
                lw["index_k_bias"][l], 1e-6)[:, None, :], pos, theta,
                rope)[:, 0]
            index_keys.append(ki)
            ki = act(index_keys[len(index_keys) - 1 - index_layer_shift])
            w = (x @ lw["index_w"][l].astype(jnp.float32)) * (
                index_heads * index_dim) ** -0.5
            if lower == "index_unweighted":
                w = jnp.ones_like(w)
            if sel is None:
                sel = jnp.full((T, index_topk), -1, jnp.int32)
            theirs = selected is not None and lower is None

            def one_block(args):
                at_b, sel_b = args                       # [Tb], [Tb, topk]
                scored = jnp.einsum("qhd,sd->hqs", act(qi[at_b]), ki)
                if lower != "no_relu":
                    scored = jax.nn.relu(scored)
                scored = jnp.einsum("hqs,qh->qs", scored, w[at_b])
                causal = pos[None, :] <= at_b[:, None]          # [Tb, T]
                scored = jnp.where(causal, scored, -jnp.inf)
                own_top, own = jax.lax.top_k(scored, k_sel)
                rows = jnp.arange(at_b.shape[0])[:, None]
                binding = at_b + 1 > index_topk
                if theirs:
                    valid = sel_b >= 0
                    where = jnp.maximum(sel_b, 0)
                    mask = jnp.zeros(causal.shape, bool).at[rows, where].max(
                        valid)
                    lowest = jnp.where(
                        valid, jnp.take_along_axis(scored, where, axis=1),
                        jnp.inf).min(axis=1)
                    short = jnp.where(binding, own_top[:, -1] - lowest, 0.0)
                    short = jnp.where((mask & ~causal).any(axis=1), jnp.inf,
                                      short)
                    want = jnp.minimum(at_b + 1, index_topk)
                    off = (jnp.abs(valid.sum(axis=1) - want)
                           + jnp.abs((mask & causal).sum(axis=1) - want))
                else:
                    mask = jnp.zeros(causal.shape, bool).at[rows, own].max(
                        own_top > -jnp.inf)
                    short = jnp.zeros(at_b.shape, jnp.float32)
                    off = jnp.zeros(at_b.shape, jnp.int32)
                mask = causal if lower == "no_select" else mask & causal
                scores = jnp.einsum("qhd,shd->hqs", qq[at_b], k) * sigma
                scores = jnp.where(mask[None], scores, _NEG)
                p = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
                p = jnp.where(mask[None], p, 0.0)
                p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
                o = jnp.einsum("hqs,shd->qhd", act(p), v)
                return o.reshape(at_b.shape[0], -1), short, off

            o, short, off = jax.lax.map(
                one_block, (blocks, sel.reshape(-1, block, index_topk)))
            out = h + act(o.reshape(T, -1)) @ _leaf(lw["wo"], l)
            return out, short.reshape(T), off.reshape(T).astype(jnp.int32)

        def swiglu(x, gate, up, down):
            return act(jax.nn.silu(x @ gate) * (x @ up)) @ down

        key_short, key_off = [], []

        def attend(lw, l, layer, h):
            h, short, off = attention(
                lw, l, h, None if selected is None else selected[layer])
            key_short.append(short)
            key_off.append(off)
            return h

        dw = weights["dense"]
        for l in range(dense_layers):
            h = attend(dw, l, l, h)
            x = act(_rms_norm(h, dw["mlp_norm"][l], eps))
            h = h + swiglu(x, _leaf(dw["gate"], l), _leaf(dw["up"], l),
                           _leaf(dw["down"], l))

        lw = weights["layers"]
        held = lw["moe_gate"]["q"].shape[1]
        shortfalls = []
        for l in range(moe_layers):
            h = attend(lw, l, dense_layers + l, h)
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
        return (h @ _dequant(weights["lm_head"]),
                jnp.stack(shortfalls) if shortfalls
                else jnp.zeros((0, T), jnp.float32),
                jnp.stack(key_short), jnp.stack(key_off))


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config,
    at a depth of ``layers`` (the leading dense layers first)."""
    dense = min(int(cfg["first_k_dense_replace"]), layers)
    return {"dense_layers": dense, "moe_layers": layers - dense,
            "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v_dim": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "gamma": float(cfg["routed_scaling_factor"]),
            "expert_offset": int(cfg["serving"].get("expert_offset", 0)),
            "index_heads": int(cfg["index_n_heads"]),
            "index_dim": int(cfg["index_head_dim"]),
            "index_topk": int(cfg["index_topk"])}
