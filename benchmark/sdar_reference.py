"""The plain reference of SDAR-MoE: a forward pass over a whole sequence in
float32 ``jax.numpy``, and a plain generate loop.

Written from the published ``config.json`` (``model_type: sdar_moe``) and the
model card's description of generation; what neither states is listed in the
configuration's ``assumed``. Per layer, pre-norm:

    h <- h + W_o Attn(x),   x = RMSNorm(h)
        q = x W_q as heads of head_dim, k = x W_k, v = x W_v; per head
        q <- RMSNorm(q) g_q, k <- RMSNorm(k) g_k, then the rotary embedding
        (rotate-half); scores q.k / sqrt(head_dim); key j is visible to query
        i iff floor(j / Lb) <= floor(i / Lb); softmax; . V
    h <- h + sum_{e in top-K} g_e W_down,e(silu(W_gate,e x') * W_up,e x'),
        x' = RMSNorm(h); s = softmax(x' W_r) over all experts in float32; the
        K largest; g = s_top / sum(s_top)

then the final RMSNorm and the untied head. The logits at position i are for
the token AT i.

No cache, no kernel, no batching, no bfloat16: one sequence at a time, every
matrix product at ``highest`` precision, every expert computed for every
token and combined by a gate that is zero off the chosen. It is handed int8
tensors and their scales and dequantises them itself. It imports nothing from
the program.

``choices`` [layers, T, K] hands the forward the experts to use in place of
its own K largest (the adapter passes the program's: ``adapters/sdar.py``
says why); the gates stay the reference's own scores renormalised over them,
and the forward also returns, per position, how far the lowest-scored of them
lies under the reference's own K-th score, in units of the router's logits.
``lower`` (``"kv_int8"``, ``"fp8"``) is ``reference.py``'s: the same forward
with one thing kept one precision below what the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import _dequant, _int8_rows, _rms_norm, _rope


def _layer_leaf(w: dict, l: int) -> dict:
    return {"q": w["q"][l], "s": w["s"][l]}


@functools.partial(jax.jit, static_argnames=(
    "layers", "heads", "kv_heads", "head_dim", "eps", "theta", "block",
    "top_k", "lower"))
def forward_logits(weights, ids, at, choices=None, *, layers, heads, kv_heads,
                   head_dim, eps, theta, block, top_k, lower=None):
    """(logits [len(at), V], shortfall [layers, T]) of one sequence ``ids``
    [T] under the block mask, at positions ``at``."""
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
        mask = (pos[None, :] // block) <= (pos[:, None] // block)
        group = heads // kv_heads
        lw = weights["layers"]
        shortfalls = []
        for l in range(layers):
            x = act(_rms_norm(h, lw["attn_norm"][l], eps))
            q = (x @ _dequant(_layer_leaf(lw["wq"], l))).reshape(T, heads, head_dim)
            k = (x @ _dequant(_layer_leaf(lw["wk"], l))).reshape(T, kv_heads, head_dim)
            v = (x @ _dequant(_layer_leaf(lw["wv"], l))).reshape(T, kv_heads, head_dim)
            q = _rope(_rms_norm(q, lw["q_norm"][l], eps), pos, theta)
            k = _rope(_rms_norm(k, lw["k_norm"][l], eps), pos, theta)
            if lower == "kv_int8":
                k, v = _int8_rows(k), _int8_rows(v)
            q, k, v = act(q), act(k), act(v)
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
            scores = jnp.einsum("ihd,jhd->hij", q, k) / head_dim ** 0.5
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
            attn = jnp.einsum("hij,jhd->ihd", act(probs), v).reshape(T, -1)
            h = h + act(attn) @ _dequant(_layer_leaf(lw["wo"], l))

            x = act(_rms_norm(h, lw["mlp_norm"][l], eps))
            logit = x @ lw["router"][l].astype(jnp.float32)          # [T, E]
            s = jax.nn.softmax(logit, axis=-1)
            own_top, own = jax.lax.top_k(logit, top_k)
            chosen = own if choices is None else choices[l]
            picked = jnp.take_along_axis(logit, chosen, axis=1)      # [T, K]
            shortfalls.append(own_top[:, -1] - picked.min(axis=1))
            s_top = jnp.take_along_axis(s, chosen, axis=1)
            gates = jnp.zeros_like(s).at[
                jnp.arange(T)[:, None], chosen].set(
                    s_top / s_top.sum(axis=1, keepdims=True))        # [T, E]

            def expert(acc, e):
                g, u, d = (_dequant({"q": lw[n]["q"][l][e], "s": lw[n]["s"][l][e]})
                           for n in ("moe_gate", "moe_up", "moe_down"))
                y = act(jax.nn.silu(x @ g) * (x @ u)) @ d
                return acc + gates[:, e][:, None] * y, None

            out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                                  jnp.arange(s.shape[1]))
            h = h + out
        h = act(_rms_norm(h[at], weights["final_norm"], eps))
        return h @ _dequant(weights["lm_head"]), jnp.stack(shortfalls)


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward_logits`` from a published config and
    the generation defaults the configuration's file gives."""
    return {"layers": layers, "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
            "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
            "block": int(cfg["serving"]["block_length"]),
            "top_k": int(cfg["num_experts_per_tok"])}


def unmask_step(block, x0, conf, mask_id: int, per_step: int,
                dynamic: bool, threshold: float):
    """One denoising step on plain lists: ``block`` the open block's tokens,
    ``x0``/``conf`` the drawn token and its confidence at every position.
    Static: the ``per_step`` most confident masked positions; dynamic: every
    masked position over ``threshold`` and at least those. Ties go to the
    earlier position."""
    masked = [i for i, t in enumerate(block) if t == mask_id]
    by_conf = sorted(masked, key=lambda i: (-conf[i], i))
    take = set(by_conf[:per_step])
    if dynamic:
        take |= {i for i in masked if conf[i] > threshold}
    return [x0[i] if i in take else t for i, t in enumerate(block)]


def generate(logits_of, prompt: list[int], max_tokens: int, *, block: int,
             steps: int, mask_id: int, dynamic: bool = False,
             threshold: float = 0.9, stops=()) -> list[int]:
    """The plain generate loop, greedy: ``logits_of(tokens) -> [T, V]`` is a
    whole forward of the sequence so far (its open block included). The
    prompt's leftover opens the first block; a block is denoised until no
    mask is left, then the next opens. Returns the generated tokens, cut at
    ``max_tokens`` or after the first stop id."""
    import numpy as np

    seq = list(prompt)
    start = len(seq) - len(seq) % block
    out: list[int] = []
    while True:
        open_block = seq[start:] + [mask_id] * (block - len(seq[start:]))
        while mask_id in open_block:
            lg = np.array(logits_of(seq[:start] + open_block),
                          np.float64)[start:start + block]
            lg[:, mask_id] = -np.inf     # the mask token is never drawn
            p = np.exp(lg - lg.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            x0 = lg.argmax(-1).tolist()
            conf = [float(p[i, x0[i]]) for i in range(block)]
            open_block = unmask_step(open_block, x0, conf, mask_id,
                                     block // steps, dynamic, threshold)
        for tok in open_block[len(seq) - start:]:
            out.append(tok)
            if tok in stops or len(out) >= max_tokens:
                return out
        seq = seq[:start] + open_block
        start += block
