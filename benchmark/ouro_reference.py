"""The plain reference of the Ouro block: a looped decoder's forward pass in
float32 ``jax.numpy``.

Written from the published ``config.json`` (``model_type: ouro``:
``total_ut_steps`` 4, ``early_exit_threshold`` 1, plain multi-head attention,
rotary 1e6, SwiGLU, ``rms_norm_eps`` 1e-6, untied head) and, for what the
config does not settle, from the family's modelling code and report (arXiv
2510.25741) as ISSUE 52 set it down; every such line is under ``assumed`` in
the configuration's file:

    h = E[ids]
    for t in 1..R:                     the SAME L layers' weights in every pass
      for l in 1..L:
        a = W_o Attn_l(RMSNorm(h; g1_l))          no bias; rotary on q and k
        h = h + RMSNorm(a; g2_l)                  the branch normed AGAIN
        m = W_down_l(silu(W_gate_l x) * W_up_l x),  x = RMSNorm(h; g3_l)
        h = h + RMSNorm(m; g4_l)
      h = RMSNorm(h; g_final)                     after EVERY pass
      lam_t = sigmoid(w_exit . h + b_exit)
    logits = W_head h                             h of the pass taken

    p_t = lam_t prod_{s<t}(1 - lam_s) (t < R), p_R = prod_{s<R}(1 - lam_s);
    the pass taken is the first whose cumulated p reaches the threshold.

No cache, no kernel, no batching, no bfloat16: one sequence at a time, every
matrix product at ``highest`` precision, every pass recomputing its own K and
V over the whole sequence (which is what "pass t attends over what pass t
cached" means without a cache). It is handed int8 tensors and their scales
and dequantises them itself. It imports nothing from the program.

``lower`` turns it into a control: ``fp8`` and ``kv_int8`` as
``reference.py``'s, and two of this architecture's own, each a DIFFERENT
model and so over any limit: ``loop_3`` runs one pass fewer and reads the
head there, ``no_pass_norm`` leaves ``g_final`` out between passes (it stays
ahead of the head and of the gate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import _dequant, _int8_rows, _rms_norm, _rope

CONTROLS = ("kv_int8", "fp8", "loop_3", "no_pass_norm")


def _leaf(w: dict, l: int) -> jnp.ndarray:
    return _dequant({"q": w["q"][l], "s": w["s"][l]})


def exit_probabilities(lam: jnp.ndarray) -> jnp.ndarray:
    """``p`` [R, T] of the gate values ``lam`` [R - 1, T] (the last pass has
    no say: it takes what is left)."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]])
    return jnp.concatenate([lam * before, stay[-1:]])


def exit_pass_taken(lam: jnp.ndarray, threshold: float) -> jnp.ndarray:
    """The 1-based pass each position leaves at: the first whose cumulated
    ``p`` reaches ``threshold``, the last where none does short of it (at a
    threshold of 1 always the last: a sigmoid is under 1). [T] int32."""
    cum = jnp.cumsum(exit_probabilities(lam), axis=0)[:-1]
    return 1 + jnp.sum(cum < threshold, axis=0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "layers", "passes", "heads", "kv_heads", "head_dim", "eps", "theta",
    "threshold", "lower"))
def forward(weights, ids, at, *, layers, passes, heads, kv_heads, head_dim,
            eps, theta, threshold=1.0, lower=None):
    """(logits [len(at), V], lam [passes - 1, len(at)]) of one sequence
    ``ids`` [T] at positions ``at``: the head over the hidden of the pass
    each position takes, and the exit gate's values after passes
    ``1 .. passes - 1``."""
    if lower not in (None, *CONTROLS):
        raise ValueError(f"unknown control {lower!r}")
    if lower == "loop_3":
        passes -= 1

    def act(x):     # the input of a matrix product
        if lower == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    with jax.default_matmul_precision("highest"):
        T = ids.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        emb = weights["embed"]
        h = emb["qe"][ids].astype(jnp.float32) * emb["se"][ids][:, None]
        mask = pos[None, :] <= pos[:, None]
        group = heads // kv_heads
        lw, gate = weights["layers"], weights["exit_gate"]
        normed, lams = [], []
        for t in range(passes):
            for l in range(layers):
                x = act(_rms_norm(h, lw["attn_norm"][l], eps))
                q = _rope((x @ _leaf(lw["wq"], l)).reshape(T, heads, head_dim),
                          pos, theta)
                k = _rope((x @ _leaf(lw["wk"], l)).reshape(T, kv_heads,
                                                           head_dim),
                          pos, theta)
                v = (x @ _leaf(lw["wv"], l)).reshape(T, kv_heads, head_dim)
                if lower == "kv_int8":
                    k, v = _int8_rows(k), _int8_rows(v)
                q, k, v = act(q), act(k), act(v)
                k = jnp.repeat(k, group, axis=1)
                v = jnp.repeat(v, group, axis=1)
                scores = jnp.einsum("ihd,jhd->hij", q, k) / head_dim ** 0.5
                probs = jax.nn.softmax(
                    jnp.where(mask[None], scores, -jnp.inf), axis=-1)
                attn = jnp.einsum("hij,jhd->ihd", act(probs), v).reshape(
                    T, heads * head_dim)
                a = act(attn) @ _leaf(lw["wo"], l)
                h = h + _rms_norm(a, lw["attn_post_norm"][l], eps)
                x = act(_rms_norm(h, lw["mlp_norm"][l], eps))
                m = act(jax.nn.silu(x @ _leaf(lw["gate"], l))
                        * (x @ _leaf(lw["up"], l))) @ _leaf(lw["down"], l)
                h = h + _rms_norm(m, lw["mlp_post_norm"][l], eps)
            out = _rms_norm(h, weights["final_norm"], eps)
            if lower != "no_pass_norm":
                h = out
            normed.append(out[at])
            if t < passes - 1:
                lams.append(jax.nn.sigmoid(
                    out[at] @ gate["w"].astype(jnp.float32) + gate["b"]))
        lam = jnp.stack(lams) if lams else jnp.zeros((0, at.shape[0]))
        taken = (exit_pass_taken(lam, threshold) if lams
                 else jnp.ones(at.shape, jnp.int32))
        hidden = jnp.take_along_axis(
            jnp.stack(normed), (taken - 1)[None, :, None], axis=0)[0]
        return act(hidden) @ _dequant(weights["lm_head"]), lam


def forward_logits(weights, ids, at, *, lower=None, **kw):
    """The logits alone: what the judge compares."""
    return forward(weights, ids, at, lower=lower, **kw)[0]


def reference_kwargs(cfg: dict, layers: int) -> dict:
    """The static arguments of ``forward`` from a published config, at
    ``layers`` layers of the stack."""
    return {"layers": layers, "passes": cfg["total_ut_steps"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "threshold": float(cfg["early_exit_threshold"])}
