"""Nemotron-H (``model_type: nemotron_h``; Nemotron-3-Super): a stack whose
every layer is ONE sub-layer behind ONE norm. ``cfg.layer_types`` names each
layer a Mamba-2 mixer (``M`` of ``hybrid_override_pattern``), an attention
layer without rotary (``*``) or an expert layer (``E``); no layer is a mixer
AND a feed-forward part. Written from the published config; RMSNorm, pre-norm
residual, no bias but the conv's, an untied head.

    h_0 = Embed(ids);   x = RMSNorm_l(h)
    mamba:      [z | xBC | dt] = x W_in;  xBC = SiLU(conv1d(xBC) + b)
                Δ = softplus(dt + dt_bias);  A = −exp(A_log)
                S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_{g,t}ᵀ;  y_t = S_t C_{g,t} + D x_t
                m = W_out · RMSNorm_groups(y ⊙ SiLU(z))
    attention:  m = W_o · softmax(q kᵀ / √d + causal) v          (no rotary)
    moe:        s = sigmoid(x W_g) in float32; the K largest of s + b
                g_e = γ s_e / Σ_chosen s
                u = x W_ld                                        (H → latent)
                r = Σ_{e chosen, held} g_e · W2_e relu(W1_e u)²   (no gate)
                m = r W_lu + Ws2 relu(Ws1 x)²                     (latent → H)
    h ← h + m;   logits = RMSNorm_f(h) W_head

**The expert layer computes in the latent**: every token is projected down
once (dense), ``llama.moe_experts`` sorts, gathers, multiplies and
scatter-adds rows of the LATENT width (its two-matrix form: the tree holds no
``moe_gate``), and the sum is projected up once. The up-projection is linear,
so a chip's share of the routed part (``cfg.experts_held``, as kimi_k2's)
plus the other chips' adds up to the whole layer; the shared expert and the
router see the full hidden and are whole here.

**The caches and the expert stack follow the kinds**, as granite_hybrid's
caches do: the page pool has ``cfg.kv_layers`` layers, the state slab
``cfg.state_layers``, and ``params["moe"]`` ``cfg.moe_layers``, each indexed
by a layer's index AMONG THE LAYERS OF ITS KIND (stacked over all layers the
served cut's experts would be 15.5 GB). ``params["layers"]`` holds what every
layer has: its norm.

**Depth.** Kinds alternate (``M E M E``), so runs of one kind have length 1
and granite's scan over runs would trace 22 bodies for 22 layers. Here a run
is a UNIT repeated (``layer_runs``): a unit of one kind, or of two kinds that
alternate, so ``MEMEMEM*EMEMEMEM*EMEME`` is seven scans of ten sub-layer
bodies (``(ME)³ M * (EM)⁴ * (EM)² E``), and the 88 layers 19 scans. A branch
by kind inside ONE scan (``lax.switch``) was not taken: a conditional hands
the pools and the slab to every branch and takes them back, and whether the
branches that leave them alone alias them or copy 4 GB a layer is the
compiler's choice, not the program's. A unit's body is straight-line code;
pools and slab are scan carries that the layers not of their kind never
name. Each body picks its layers out of the whole stacks by index, as
granite_hybrid's does.

The two sub-layers that hold a cache ARE granite_hybrid's (its forwards are
called with this module's ``_run_layers``); the expert layer is
``llama.moe_route``'s sigmoid branch, ``llama.moe_experts`` and kimi_k2's
share. ``aux`` as kimi_k2's: the experts chosen ``[moe_layers, N, K]`` and
``STEP_COUNTERS``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from . import granite_hybrid
from .configs import ModelConfig
from .falcon_h1 import init_mixer_small, init_state
from .granite_hybrid import _at
from .kimi_k2 import _proj
from .llama import (Params, _act, gather_last_hidden, lm_head_logits,
                    moe_experts, moe_item_rows, moe_route, moe_share_counts,
                    split_moe)

__all__ = ["init_params", "init_params_with", "init_state", "layer_runs",
           "forward_paged_decode", "forward_paged_mixed", "lm_head_logits",
           "gather_last_hidden", "STEP_COUNTERS"]

#: what ``aux`` counts over a forward's expert layers, in the order the
#: serving programs hand them to the host: assignments routed (tokens x K),
#: those that fell on experts held here, held experts with at least one, the
#: rows one grouped matmul of the layer multiplied (``llama.moe_item_rows``:
#: its work items x its row tile), and the held assignments once more where
#: the forward was a decode step (0 in a mixed step: with ``touched`` over
#: decode chunks it says how many rows a touched expert has)
STEP_COUNTERS = ("assignments", "local", "touched", "item_rows",
                "decode_local")
#: those of them a layer counts (the forwards add the last)
_LAYER_COUNTERS = STEP_COUNTERS[:-1]

_KINDS = ("mamba", "attention", "moe")


def _repeats(kinds: tuple, at: int, width: int) -> tuple[tuple, int]:
    """The ``width`` kinds at ``at`` and how often they repeat from there."""
    unit, reps = kinds[at: at + width], 1
    while kinds[at + reps * width: at + (reps + 1) * width] == unit:
        reps += 1
    return unit, reps


def layer_runs(cfg: ModelConfig) -> list[tuple[tuple, int, dict, int]]:
    """The stack as runs of a repeated unit: (the unit's kinds, first layer,
    the first index among the layers of each kind, repetitions). A unit is
    one kind, or two kinds that alternate at least twice where that covers
    more layers than the run of the first kind alone."""
    kinds = cfg.layer_types
    runs, seen, at = [], dict.fromkeys(_KINDS, 0), 0
    while at < len(kinds):
        unit, reps = _repeats(kinds, at, 1)
        pair, twice = _repeats(kinds, at, 2)
        if len(set(pair)) == 2 and twice > 1 and 2 * twice > reps:
            unit, reps = pair, twice
        runs.append((unit, at, dict(seen), reps))
        for kind in unit:
            seen[kind] += reps
        at += reps * len(unit)
    return runs


# ---------------------------------------------------------------- parameters
def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """The parameter tree, its matrices made by ``matmul(key, shape)`` (the
    contraction on axis -2) and its embedding by ``embed(key, shape)``:
    ``init_params`` draws them in ``dtype``, ``runtime/quant.py`` straight
    into int8. Norms are ones; the router float32 at ``hidden^-1/2`` and its
    selection bias zero; the mixer's small leaves float32 as falcon_h1 draws
    them. Embedding and head are the held rows of the vocabulary, the expert
    matrices the held experts."""
    H, Vh, L = cfg.hidden_size, cfg.vocab_rows, cfg.num_layers
    Lm, La, Le = cfg.state_layers, cfg.kv_layers, cfg.moe_layers
    E, El, I = cfg.num_experts, cfg.experts_local, cfg.intermediate_size
    W, Is = cfg.expert_row_width, cfg.shared_intermediate_size
    Dq, Dkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 20))
    return {
        "embed": embed(next(keys), (Vh, H)),
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": matmul(next(keys), (H, Vh)),
        "layers": {"norm": jnp.ones((L, H), dtype)},
        "mamba": {"ssm_in": matmul(next(keys), (Lm, H, cfg.ssm_proj_dim)),
                  "ssm_out": matmul(next(keys), (Lm, cfg.ssm_inner, H)),
                  **init_mixer_small(cfg, next(keys))},
        "attention": {"wq": matmul(next(keys), (La, H, Dq)),
                      "wk": matmul(next(keys), (La, H, Dkv)),
                      "wv": matmul(next(keys), (La, H, Dkv)),
                      "wo": matmul(next(keys), (La, Dq, H))},
        "moe": {"router": jax.random.normal(next(keys), (Le, H, E),
                                            jnp.float32) * H ** -0.5,
                "router_bias": jnp.zeros((Le, E), jnp.float32),
                "latent_down": matmul(next(keys), (Le, H, W)),
                "latent_up": matmul(next(keys), (Le, W, H)),
                "shared_up": matmul(next(keys), (Le, H, Is)),
                "shared_down": matmul(next(keys), (Le, Is, H)),
                "moe_up": matmul(next(keys), (Le, El, W, I)),
                "moe_down": matmul(next(keys), (Le, El, I, W))}}


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


# ------------------------------------------------------------------- a layer
def _experts(lp: dict, moe: dict, i, h: jnp.ndarray, x: jnp.ndarray,
             cfg: ModelConfig):
    """An expert layer on expert-stack layer ``i`` over the normed ``x``
    [1, N, H], added to ``h``; also the experts chosen [N, K] and the layer's
    ``_LAYER_COUNTERS``."""
    flat = x.reshape(-1, x.shape[-1])
    top_idx, gates = moe_route(
        flat, lp["router"], cfg.experts_per_token, sigmoid=True,
        bias=lp["router_bias"], scale=cfg.routed_scaling_factor)
    latent = _proj(flat, lp["latent_down"]).astype(flat.dtype)
    routed = moe_experts(latent, top_idx, gates, moe, cfg, i)
    shared = _act(_proj(flat, lp["shared_up"]), cfg).astype(flat.dtype)
    y = (_proj(routed.astype(flat.dtype), lp["latent_up"])
         + _proj(shared, lp["shared_down"]))
    counts = jnp.stack([*moe_share_counts(top_idx, cfg),
                        moe_item_rows(top_idx, cfg)])
    return h + y.reshape(h.shape).astype(h.dtype), top_idx, counts


def _run_layers(params: Params, cfg: ModelConfig, h, pools, state,
                mix: Callable, attend: Callable):
    """The stack, a run at a time (``layer_runs``); ``mix`` and ``attend``
    are what ``granite_hybrid._run_layers`` is handed. Returns (h, pools,
    state, aux)."""
    norms = params["layers"]["norm"]
    small, moe = split_moe(params["moe"])
    carry = (h, *pools, state["ssm"], state["conv"])
    experts, counts = [], jnp.zeros((len(_LAYER_COUNTERS),), jnp.int32)

    for unit, first, first_of, reps in layer_runs(cfg):
        def body(carry, step, unit=unit, first=first, first_of=first_of):
            h, k_pool, v_pool, ssm, conv = carry
            chosen, n = [], jnp.zeros_like(counts)
            for j, kind in enumerate(unit):
                layer = first + step * len(unit) + j
                i = first_of[kind] + step
                x = rms_norm(h, jax.lax.dynamic_index_in_dim(
                    norms, layer, 0, keepdims=False), cfg.rms_norm_eps)
                if kind == "mamba":
                    h, ssm, conv = mix(_at(params["mamba"], i), i, h, x, ssm,
                                       conv)
                elif kind == "attention":
                    h, k_pool, v_pool = attend(_at(params["attention"], i),
                                               i, h, x, k_pool, v_pool)
                else:
                    h, top_idx, c = _experts(_at(small, i), moe, i, h, x, cfg)
                    chosen.append(top_idx)
                    n = n + c
            return (h, k_pool, v_pool, ssm, conv), (chosen, n)

        carry, (chosen, n) = jax.lax.scan(
            body, carry, jnp.arange(reps, dtype=jnp.int32))
        experts += chosen          # one [reps, N, K] for the unit's moe layer
        counts = counts + jnp.sum(n, axis=0)

    h, k_pool, v_pool, ssm, conv = carry
    aux = {"experts": jnp.concatenate(experts),
           **{name: counts[i] for i, name in enumerate(_LAYER_COUNTERS)}}
    return h, (k_pool, v_pool), {"ssm": ssm, "conv": conv}, aux


# ------------------------------------------------------------------ forwards
def forward_paged_decode(params: Params, cfg: ModelConfig, *args, **kwargs):
    """``granite_hybrid.forward_paged_decode`` over this module's stack:
    one decode step over the page pool and the state slab, (hidden [B, 1, H],
    pools, state, aux)."""
    h, pools, state, aux = granite_hybrid.forward_paged_decode(
        params, cfg, *args, run_layers=_run_layers, **kwargs)
    return h, pools, state, {**aux, "decode_local": aux["local"]}


def forward_paged_mixed(params: Params, cfg: ModelConfig, *args, **kwargs):
    """``granite_hybrid.forward_paged_mixed`` over this module's stack: one
    ragged mixed step, (hidden, pools, state, aux)."""
    h, pools, state, aux = granite_hybrid.forward_paged_mixed(
        params, cfg, *args, run_layers=_run_layers, **kwargs)
    return h, pools, state, {**aux, "decode_local": jnp.zeros((), jnp.int32)}
