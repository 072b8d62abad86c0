"""Solar-Open2 (``model_type: solar_open2``): a stack of which three layers in
four are KDA, linear attention under a gated delta rule with a decay for
every key channel, and the fourth is GQA attention without rotary whose
output passes a sigmoid gate; after EVERY mixer, sigmoid-routed experts
beside a shared expert. Written from the published config, the Kimi Linear
report (arXiv 2510.26692) and ``fla/layers/kda.py`` for KDA; RMSNorm,
pre-norm residual, no bias anywhere, an untied head.

    h_0 = Embed(ids);   x = RMSNorm(h)
    kda:        q, k, v = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v))
                q̂ = q / ‖q‖ · d^-1/2;  k̂ = k / ‖k‖                    (a head)
                g = −exp(A_log) · softplus(x W_f1 W_f2 + dt_bias);  α = exp(g)
                β = 2 sigmoid(x W_β)               (kda_allow_neg_eigval: the 2)
                S̃ = Diag(α) S_{t-1};  S_t = S̃ + β k̂ (v − S̃ᵀ k̂)ᵀ;  o = S_tᵀ q̂
                m = W_o [RMSNorm_head(o) ⊙ sigmoid(x W_g1 W_g2)]
    attention:  m = W_o [softmax(q kᵀ / √d + causal) v ⊙ sigmoid(x W_gate)]
    h ← h + m
    x′ = RMSNorm(h);  s = sigmoid(x′ W_r) in float32; the K largest of s + b
    g_e = s_e / Σ_chosen s
    h ← h + SwiGLU_shared(x′) + Σ_{e chosen, held} g_e SwiGLU_e(x′)
    logits = RMSNorm(h) W_head

**The two forwards are granite_hybrid's** (``run_layers=`` and ``mixer=``):
the layer that holds state runs this module's four mixer functions where
granite's runs falcon_h1's, on the same slab seam: ``{"ssm": [kda layers,
rows, heads, keys, values], "conv": [kda layers, rows, (taps - 1) x q k v
channels]}`` f32 (``init_state`` says why the tail is flat), a decode step in place under ``ops/kda.kda_state_update``,
a prompt's chunk through ``ops/kda.kda_chunked`` on the lane's own row. An
attention layer is granite's with one leaf more (``w_gate``). The expert
layer is kimi_k2's (``_moe_residual``: ``llama.moe_route``'s sigmoid branch,
``llama.moe_experts`` told which experts it holds, the compact branch), with
kimi_k2's ``STEP_COUNTERS``.

**The caches follow the kinds**, as granite_hybrid's do: the page pool has
``cfg.kv_layers`` layers and the slab ``cfg.state_layers``, each indexed by
a layer's index AMONG THE LAYERS OF ITS KIND. ``params["layers"]`` is
stacked over ALL layers (both norms, the router and its selection bias, the
shared expert, the held experts), ``params["kda"]`` and
``params["attention"]`` over the layers of their kind.

**Depth.** The stack is runs of a repeated UNIT (``layer_runs``): ``a k k k``
twelve times is ONE ``lax.scan`` whose body is four layers, straight-line,
each picking its layer out of the whole stacks by index (granite_hybrid's and
nemotron_h's notes on depth say why).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kda import kda_chunked, kda_state_update
from ..ops.norms import rms_norm
from ..ops.ssd import causal_conv, causal_conv_step
from . import granite_hybrid
from .configs import ModelConfig
from .falcon_h1 import (State, _lane_rows, _layer_rows, _store_lane_rows,
                        _store_rows)
from .granite_hybrid import Mixer, _at
from .kimi_k2 import STEP_COUNTERS, _moe_residual, _proj
from .llama import Params, gather_last_hidden, lm_head_logits, split_moe

__all__ = ["init_params", "init_params_with", "init_state", "layer_runs",
           "forward_paged_decode", "forward_paged_mixed", "lm_head_logits",
           "gather_last_hidden", "STEP_COUNTERS"]


def layer_runs(cfg: ModelConfig) -> list[tuple[tuple, int, dict, int]]:
    """The stack as runs of a repeated unit: (the unit's kinds, first layer,
    the first index among the layers of each kind, repetitions). At each
    point the unit is the one that, repeated at least twice, covers the most
    layers (the shorter of two that cover as many), else the one layer
    there: ``a k k k`` x 12 is one run, ``a k k k`` alone is ``a`` and
    ``k`` x 3."""
    kinds = cfg.layer_types
    runs, seen, at = [], dict.fromkeys(sorted(set(kinds)), 0), 0
    while at < len(kinds):
        width, reps = 1, 1
        for w in range(1, (len(kinds) - at) // 2 + 1):
            r = 1
            while kinds[at + r * w: at + (r + 1) * w] == kinds[at: at + w]:
                r += 1
            if r > 1 and w * r > width * reps:
                width, reps = w, r
        unit = kinds[at: at + width]
        runs.append((unit, at, dict(seen), reps))
        for kind in unit:
            seen[kind] += reps
        at += reps * width
    return runs


# ---------------------------------------------------------------- parameters
def init_kda_small(cfg: ModelConfig, key: jax.Array) -> dict[str, jnp.ndarray]:
    """A kda layer's small f32 leaves, drawn as falcon_h1's are so that a
    synthetic model's decays are neither 0 nor 1: ``A_log = log U(1, 16)`` a
    head and ``dt_bias`` a channel the inverse softplus of a step log-uniform
    in [1e-3, 1e-1]; conv taps U(±taps^-1/2), no bias; the head norm's
    weight 1."""
    L, Hs, K = cfg.state_layers, cfg.ssm_heads, cfg.ssm_conv
    k = jax.random.split(key, 3)
    step = jnp.exp(jax.random.uniform(
        k[0], (L, Hs * cfg.ssm_head_dim), jnp.float32, np.log(1e-3),
        np.log(1e-1)))
    return {
        "A_log": jnp.log(jax.random.uniform(k[1], (L, Hs), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "conv_w": jax.random.uniform(k[2], (L, K, cfg.ssm_conv_dim),
                                     jnp.float32, -K ** -0.5, K ** -0.5),
        "o_norm": jnp.ones((L, cfg.ssm_state), jnp.float32),
    }


def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """The parameter tree, its matrices made by ``matmul(key, shape)`` (the
    contraction on axis -2) and its embedding by ``embed(key, shape)``:
    ``init_params`` draws them in ``dtype``, ``runtime/quant.py`` straight
    into int8. Norms are ones; the router float32 at ``hidden^-1/2`` and its
    selection bias zero; a kda layer's small leaves float32. Embedding and
    head are the held rows of the vocabulary, the expert matrices the held
    experts."""
    H, Vh, L = cfg.hidden_size, cfg.vocab_rows, cfg.num_layers
    Lk, La = cfg.state_layers, cfg.kv_layers
    E, El, I, Is = cfg.num_experts, cfg.experts_local, cfg.expert_width, \
        cfg.shared_width
    Dq, Dkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 32))
    return {
        "embed": embed(next(keys), (Vh, H)),
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": matmul(next(keys), (H, Vh)),
        "kda": {**{name: matmul(next(keys), (Lk, *shape))
                   for name, shape in cfg.kda_matrices().items()},
                **init_kda_small(cfg, next(keys))},
        "attention": {"wq": matmul(next(keys), (La, H, Dq)),
                      "wk": matmul(next(keys), (La, H, Dkv)),
                      "wv": matmul(next(keys), (La, H, Dkv)),
                      "wo": matmul(next(keys), (La, Dq, H)),
                      # the output gate is there where the tree holds it
                      **({"w_gate": matmul(next(keys), (La, H, Dq))}
                         if cfg.use_gqa_gate else {})},
        "layers": {
            "attn_norm": jnp.ones((L, H), dtype),
            "mlp_norm": jnp.ones((L, H), dtype),
            "router": jax.random.normal(next(keys), (L, H, E), jnp.float32)
            * H ** -0.5,
            "router_bias": jnp.zeros((L, E), jnp.float32),
            "shared_gate": matmul(next(keys), (L, H, Is)),
            "shared_up": matmul(next(keys), (L, H, Is)),
            "shared_down": matmul(next(keys), (L, Is, H)),
            "moe_gate": matmul(next(keys), (L, El, H, I)),
            "moe_up": matmul(next(keys), (L, El, H, I)),
            "moe_down": matmul(next(keys), (L, El, I, H))}}


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Random-init parameters at model shape, every matrix at
    ``fan_in^-1/2``."""
    def matmul(k, shape):
        return jax.random.normal(k, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)

    def embed(k, shape):
        return jax.random.normal(k, shape, dtype) * jnp.asarray(
            shape[-1] ** -0.5, dtype)

    return init_params_with(cfg, key, dtype, matmul, embed)


def init_state(cfg: ModelConfig, rows: int) -> State:
    """The zero state slab for ``rows`` rows (slots first, then snapshots),
    one layer for every kda layer. **A row's conv tail is stored FLAT**,
    ``[taps - 1] x [q k v channels]`` in one minor dimension: as ``[L, rows,
    3, 24576]`` the device tiles the 3 up to 4 sublanes, and under the
    memory pressure of the served cut XLA then re-tiles the whole 212 MB leaf
    into a denser layout and back around every scan step (1.5 ms each way, 3
    times a decode step: PERF.md section 6, PR 45). A flat row has no padding
    to squeeze."""
    L = cfg.state_layers
    return {"ssm": jnp.zeros((L, rows, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((L, rows, (cfg.ssm_conv - 1) * cfg.ssm_conv_dim),
                              jnp.float32)}


def _tails(flat: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Rows of the slab's flat conv leaf as ``[rows, taps - 1, channels]``."""
    return flat.reshape(flat.shape[0], cfg.ssm_conv - 1, cfg.ssm_conv_dim)


# ----------------------------------------------------------- the kda mixer
def _kda_in(lp: dict, x: jnp.ndarray, cfg: ModelConfig):
    """The projections of normed ``x`` [B, T, H], all f32: the output gate's
    logits [B, T, values], the conv's input q | k | v [B, T, C], and the
    decay's logits beside β's [B, T, keys + heads]."""
    def low_rank(a, b):
        return _proj(_proj(x, lp[a]).astype(x.dtype), lp[b])

    qkv = jnp.concatenate([_proj(x, lp[w]) for w in ("wq", "wk", "wv")], -1)
    rates = jnp.concatenate([low_rank("f_a", "f_b"), _proj(x, lp["w_beta"])],
                            -1)
    return low_rank("g_a", "g_b"), qkv, rates


def _kda_operands(lp: dict, qkv: jnp.ndarray, rates: jnp.ndarray,
                  cfg: ModelConfig):
    """The recurrence's operands from the conv's output ``qkv`` [.., C] and
    ``rates`` [.., keys + heads], f32: q̂ and k̂ [.., Hs, K], v [.., Hs, V],
    the log-decays g [.., Hs, K] and β [.., Hs]."""
    Hs, K, V = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    lead = qkv.shape[:-1]
    qkv = jax.nn.silu(qkv)
    q = qkv[..., : Hs * K].reshape(*lead, Hs, K)
    k = qkv[..., Hs * K: 2 * Hs * K].reshape(*lead, Hs, K)
    v = qkv[..., 2 * Hs * K:].reshape(*lead, Hs, V)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        rates[..., : Hs * K] + lp["dt_bias"]).reshape(*lead, Hs, K)
    beta = jax.nn.sigmoid(rates[..., Hs * K:])
    if cfg.kda_allow_neg_eigval:
        beta = 2.0 * beta
    return unit(q) * K ** -0.5, unit(k), v, g, beta


def _kda_step(lp: dict, layer, cfg: ModelConfig, u, rates, ssm, conv, run,
              act, kernel: bool):
    """One token of rows ``[:B]`` of the slab (a decode step; a mixed step's
    decode group): ``u`` [B, C] and ``rates`` [B, keys + heads] f32 from
    ``_kda_in``. The conv tail moves with a row-sized slice, the state in
    place under the ``kda_state_update`` kernel; rows with ``run`` False keep
    both bit for bit. Returns (o [B, values] f32, ssm, conv)."""
    del act                                 # the recurrence is f32 throughout
    B = u.shape[0]
    tail = _tails(_layer_rows(conv, layer, B), cfg)
    qkv, new_tail = causal_conv_step(u, tail, lp["conv_w"], 0.0)
    conv = _store_rows(conv, layer, jnp.where(
        run[:, None, None], new_tail, tail).reshape(B, -1))
    q, k, v, g, beta = _kda_operands(lp, qkv, rates, cfg)
    o, ssm = kda_state_update(ssm, layer, q, k, v, g, beta, run,
                              kernel=kernel)
    return o.reshape(B, -1), ssm, conv


def _kda_chunk(lp: dict, layer, cfg: ModelConfig, u, rates, ssm, conv, rows,
               fresh, advance, span, act):
    """The lanes' chunk of a mixed step through the chunked WY form: ``u``
    [R, Qc, C] and ``rates`` [R, Qc, keys + heads] f32, each lane on its own
    row of the slab (``rows``; None: lane r = row r), from the zero state
    where ``fresh`` [R]. Only the lanes' rows are read and written; a lane
    with ``advance`` False keeps state and conv tail bit for bit. Returns
    (o [R, Qc, values] f32, ssm, conv)."""
    del act
    R, Qc = u.shape[:2]
    with jax.named_scope("kda_chunked"):
        tail = _tails(_lane_rows(conv, layer, rows, R), cfg)
        s_old = _lane_rows(ssm, layer, rows, R)
        qkv, new_tail = causal_conv(
            u, jnp.where(fresh[:, None, None], 0.0, tail), lp["conv_w"], 0.0,
            span)
        q, k, v, g, beta = _kda_operands(lp, qkv, rates, cfg)
        o, s_new = kda_chunked(
            q, k, v, g, beta,
            jnp.where(fresh[:, None, None, None], 0.0, s_old), span,
            cfg.ssm_chunk)
        conv = _store_lane_rows(conv, layer, rows, jnp.where(
            advance[:, None, None], new_tail, tail).reshape(R, -1))
        ssm = _store_lane_rows(ssm, layer, rows, jnp.where(
            advance[:, None, None, None], s_new, s_old))
    return o.reshape(R, Qc, -1), ssm, conv


def _kda_out(lp: dict, o: jnp.ndarray, gate: jnp.ndarray, cfg: ModelConfig,
             dtype) -> jnp.ndarray:
    """The head norm, then the gate, then the output projection. ``o`` and
    ``gate`` [B, T, values] f32."""
    lead = o.shape[:-1]
    heads = o.reshape(*lead, cfg.ssm_heads, cfg.ssm_state)
    var = jnp.mean(heads * heads, axis=-1, keepdims=True)
    normed = (heads * jax.lax.rsqrt(var + cfg.rms_norm_eps)
              * lp["o_norm"]).reshape(o.shape)
    return _proj((normed * jax.nn.sigmoid(gate)).astype(dtype),
                 lp["wo"]).astype(dtype)


KDA = Mixer(_kda_in, _kda_step, _kda_chunk, _kda_out)


# ------------------------------------------------------------------ the stack
def _run_layers(params: Params, cfg: ModelConfig, h, pools, state,
                mix: Callable, attend: Callable):
    """The stack, a run at a time (``layer_runs``); ``mix`` and ``attend``
    are what ``granite_hybrid._run_layers`` is handed. Returns (h, pools,
    state, aux)."""
    every, moe = split_moe(params["layers"])
    carry = (h, *pools, state["ssm"], state["conv"])
    experts, counts = [], jnp.zeros((len(STEP_COUNTERS),), jnp.int32)

    for unit, first, first_of, reps in layer_runs(cfg):
        def body(carry, step, unit=unit, first=first, first_of=first_of):
            h, k_pool, v_pool, ssm, conv = carry
            chosen, n = [], jnp.zeros_like(counts)
            for j, kind in enumerate(unit):
                layer = first + step * len(unit) + j
                i = (first_of[kind] + step * unit.count(kind)
                     + unit[:j].count(kind))
                lp = {**_at(every, layer), **_at(params[kind], i)}
                x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                if kind == "kda":
                    h, ssm, conv = mix(lp, i, h, x, ssm, conv)
                else:
                    h, k_pool, v_pool = attend(lp, i, h, x, k_pool, v_pool)
                h, top_idx, c = _moe_residual(lp, moe, layer, h, cfg)
                chosen.append(top_idx)
                n = n + c
            return (h, k_pool, v_pool, ssm, conv), (jnp.stack(chosen), n)

        carry, (chosen, n) = jax.lax.scan(
            body, carry, jnp.arange(reps, dtype=jnp.int32))
        experts.append(chosen.reshape(-1, *chosen.shape[2:]))   # layer order
        counts = counts + jnp.sum(n, axis=0)

    h, k_pool, v_pool, ssm, conv = carry
    aux = {"experts": jnp.concatenate(experts),
           **{name: counts[i] for i, name in enumerate(STEP_COUNTERS)}}
    return h, (k_pool, v_pool), {"ssm": ssm, "conv": conv}, aux


# ------------------------------------------------------------------ forwards
def forward_paged_decode(params: Params, cfg: ModelConfig, *args, **kwargs):
    """``granite_hybrid.forward_paged_decode`` over this module's stack and
    mixer: one decode step over the page pool and the state slab, (hidden
    [B, 1, H], pools, state, aux)."""
    return granite_hybrid.forward_paged_decode(
        params, cfg, *args, run_layers=_run_layers, mixer=KDA, **kwargs)


def forward_paged_mixed(params: Params, cfg: ModelConfig, *args, **kwargs):
    """``granite_hybrid.forward_paged_mixed`` over this module's stack and
    mixer: one ragged mixed step, (hidden, pools, state, aux)."""
    return granite_hybrid.forward_paged_mixed(
        params, cfg, *args, run_layers=_run_layers, mixer=KDA, **kwargs)
