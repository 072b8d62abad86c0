"""Granite-4.0-H (``model_type: granitemoehybrid``): a stack whose layers
differ in kind. ``cfg.layer_types`` names each layer a Mamba-2 mixer layer or
an attention layer WITHOUT rotary ("nope"); after either comes an expert
layer, routed experts beside a shared MLP. Written from the published config
and the Hugging Face modelling code it names; RMSNorm, pre-norm residual, no
bias but the conv's.

    h_0 = embedding_multiplier · Embed(ids)
    x = RMSNorm(h)
    mamba:      [z | xBC | dt] = x W_in;  xBC = SiLU(conv1d(xBC) + b)
                Δ = softplus(dt + dt_bias);  A = −exp(A_log)
                S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ;  y_t = S_t C_t + D x_t
                m = W_out · RMSNorm(y ⊙ SiLU(z))
    attention:  m = W_o · softmax(q kᵀ · attention_multiplier + causal) v
    h ← h + residual_multiplier · m
    x′ = RMSNorm(h);  the K largest of x′ W_g;  g = softmax over those K
    h ← h + residual_multiplier · (Σ_chosen g_e SwiGLU_e(x′) + SwiGLU_shared(x′))
    logits = RMSNorm(h) Embedᵀ / logits_scaling

**The caches follow the kinds.** A mamba layer writes no page and an
attention layer touches no state: the page pool has ``cfg.kv_layers`` layers,
indexed by the attention layer's index AMONG ATTENTION LAYERS, and the state
slab ``cfg.state_layers``, indexed by the mamba layer's index among mamba
layers (``runtime/paged.py`` builds both that way). The parameters are three
stacks: ``params["mamba"]`` and ``params["attention"]``, each over the layers
of its kind, and ``params["layers"]`` over ALL layers (the input norm, the
expert layer's norm, router, shared MLP and the stacked expert matrices).

**Depth.** The stack is cut into runs of consecutive layers of one kind
(``layer_runs``), each one ``lax.scan``: 40 layers are 9 runs, so the
compiled program grows with the runs and not with the layers. A run scans
over INDICES and picks its layer out of the whole stacks inside the body (the
dynamic slice ``lax.scan`` itself would make of its ``xs``); a static slice
of a stack in front of the scan would be a copy of those weights every step.

The mixer is ``models/falcon_h1.py``'s (``_mixer_in`` / ``_mixer_step`` /
``_mixer_chunk`` / ``_mixer_out`` over ``ops/ssd.py``: ``MAMBA2``, which a
stack of another recurrence replaces by ``mixer=``), attention
``models/llama.py``'s projections and both paged kernels with the scale
handed to them, the expert layer ``llama.moe_route`` / ``moe_experts`` and
kimi's SwiGLU. The entry points are the ones ``runtime/scheduler.py`` drives
for falcon_h1 (``state=``, ``write_mask``, a ``q_len`` of 0, a fresh lane and
an idle row behave as there), and like kimi_k2's they also return ``aux``:
the experts each token chose (``[L, N, K]``) and ``STEP_COUNTERS``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.platform import default_interpret as _default_interpret
from .configs import ModelConfig
from .falcon_h1 import (State, _mixer_chunk, _mixer_in, _mixer_out,
                        _mixer_step, init_mixer_small, init_state)
from .kimi_k2 import _proj, _swiglu
from .llama import (MOE_LEAVES, DecodeGroup, PagedPools, Params, _attn_out,
                    _decode_attend, _decode_targets, _embed_scale, _qkv_proj,
                    _ragged_attend, decode_work, embed_lookup,
                    gather_last_hidden, lm_head_logits, mixed_attention,
                    mixed_hidden_out, mixed_layout, moe_experts,
                    moe_item_rows, moe_route)

__all__ = ["init_params", "init_params_with", "init_state", "layer_runs",
           "forward_paged_decode", "forward_paged_mixed", "lm_head_logits",
           "gather_last_hidden", "STEP_COUNTERS"]

#: what ``aux`` counts over a forward's expert layers, in the order the
#: serving programs hand them to the host (kimi_k2's names: every expert is
#: held here, so ``local`` equals ``assignments``), then the rows one grouped
#: matmul of the layer multiplied (``llama.moe_item_rows``)
STEP_COUNTERS = ("assignments", "local", "touched", "item_rows")

Aux = dict[str, jnp.ndarray]


class Mixer(NamedTuple):
    """The four functions of a layer that holds state, as the forwards call
    them (``falcon_h1._mixer_in`` / ``_mixer_step`` / ``_mixer_chunk`` /
    ``_mixer_out`` say what each takes and returns): the projections of the
    normed input, one token of rows ``[:B]`` of the slab, a lane's chunk on
    its own rows, and the way out. ``models/solar_open2.py`` hands the
    forwards another (``mixer=``)."""
    into: Callable
    step: Callable
    chunk: Callable
    out: Callable


MAMBA2 = Mixer(_mixer_in, _mixer_step, _mixer_chunk, _mixer_out)


def _one_device(mesh: Any, interpret: bool | None, cfg: ModelConfig) -> bool:
    if mesh is not None:
        raise ValueError(f"{cfg.architecture} serves on one device: the "
                         "state slab has no tp sharding and the expert layer "
                         "no ep axis")
    return _default_interpret() if interpret is None else interpret


def layer_runs(cfg: ModelConfig) -> list[tuple[str, int, int, int]]:
    """The stack as runs of consecutive layers of one kind: (kind, first
    layer, first index among the layers of that kind, length)."""
    runs: list[tuple[str, int, int, int]] = []
    seen = {"mamba": 0, "attention": 0}
    for layer, kind in enumerate(cfg.layer_types):
        if runs and runs[-1][0] == kind:
            runs[-1] = (*runs[-1][:3], runs[-1][3] + 1)
        else:
            runs.append((kind, layer, seen[kind], 1))
        seen[kind] += 1
    return runs


# ---------------------------------------------------------------- parameters
def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """The parameter tree, its matrices made by ``matmul(key, shape)`` (the
    contraction on axis -2) and its embedding by ``embed(key, shape)``:
    ``init_params`` draws them in ``dtype``, ``runtime/quant.py`` straight
    into int8. Norms are ones, the router float32, the mixer's small leaves
    float32 as falcon_h1 draws them. The head is the embedding (tied)."""
    H, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    Lm, La = cfg.state_layers, cfg.kv_layers
    E, I, Is = cfg.num_experts, cfg.intermediate_size, \
        cfg.shared_intermediate_size
    Dq, Dkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 16))
    return {
        "embed": embed(next(keys), (V, H)),
        "final_norm": jnp.ones((H,), dtype),
        "mamba": {"ssm_in": matmul(next(keys), (Lm, H, cfg.ssm_proj_dim)),
                  "ssm_out": matmul(next(keys), (Lm, cfg.ssm_inner, H)),
                  **init_mixer_small(cfg, next(keys))},
        "attention": {"wq": matmul(next(keys), (La, H, Dq)),
                      "wk": matmul(next(keys), (La, H, Dkv)),
                      "wv": matmul(next(keys), (La, H, Dkv)),
                      "wo": matmul(next(keys), (La, Dq, H))},
        "layers": {
            "attn_norm": jnp.ones((L, H), dtype),
            "mlp_norm": jnp.ones((L, H), dtype),
            "router": jax.random.normal(next(keys), (L, H, E), jnp.float32)
            * H ** -0.5,
            "shared_gate": matmul(next(keys), (L, H, Is)),
            "shared_up": matmul(next(keys), (L, H, Is)),
            "shared_down": matmul(next(keys), (L, Is, H)),
            "moe_gate": matmul(next(keys), (L, E, H, I)),
            "moe_up": matmul(next(keys), (L, E, H, I)),
            "moe_down": matmul(next(keys), (L, E, I, H))}}


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
def _at(tree: dict, i) -> dict:
    """Layer ``i`` of every leaf of a stack (see the module's note on depth)."""
    return jax.tree.map(
        lambda v: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False), tree)


def _branch(h: jnp.ndarray, m: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """``h + residual_multiplier · m``, the multiply in f32."""
    return h + (m.astype(jnp.float32) * cfg.residual_multiplier).astype(
        h.dtype)


def _gated(lp: dict, x: jnp.ndarray, attn: jnp.ndarray) -> jnp.ndarray:
    """``attn ⊙ sigmoid(x W_gate)`` where the attention layer holds a
    ``w_gate`` (solar_open2's ``use_gqa_gate``); ``attn`` as it is where it
    holds none."""
    if "w_gate" not in lp:
        return attn
    return (attn * jax.nn.sigmoid(_proj(x, lp["w_gate"]))).astype(attn.dtype)


def _moe_residual(lp: dict, moe: dict, layer, h: jnp.ndarray,
                  cfg: ModelConfig):
    """The expert layer's norm, the routed experts and the shared MLP, added
    to ``h`` [1, N, H]; also the experts chosen [N, K] and the layer's
    ``STEP_COUNTERS``."""
    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    flat = x.reshape(-1, x.shape[-1])
    top_idx, gates = moe_route(flat, lp["router"], cfg.experts_per_token)
    y = moe_experts(flat, top_idx, gates, moe, cfg, layer)
    y = y + _swiglu(flat, lp["shared_gate"], lp["shared_up"],
                    lp["shared_down"], cfg)
    routed = jnp.asarray(top_idx.size, jnp.int32)
    touched = jnp.sum(jnp.bincount(top_idx.reshape(-1),
                                   length=cfg.num_experts) > 0)
    counts = jnp.stack([routed, routed, touched.astype(jnp.int32),
                        moe_item_rows(top_idx, cfg)])
    return _branch(h, y.reshape(h.shape), cfg), top_idx, counts


def _run_layers(params: Params, cfg: ModelConfig, h, pools, state,
                mix: Callable, attend: Callable):
    """The stack, a run at a time. ``mix(lp, i, h, x, ssm, conv) -> (h, ssm,
    conv)`` is a mamba layer's mixer on slab layer ``i``; ``attend(lp, i, h,
    x, k_pool, v_pool) -> (h, k_pool, v_pool)`` an attention layer on pool
    layer ``i``; ``x`` is the layer's normed input and the ``h`` handed back
    has the layer's branch added. Returns (h, pools, state, aux)."""
    layers = params["layers"]
    every = {k: v for k, v in layers.items() if k not in MOE_LEAVES}
    moe = {k: layers[k] for k in MOE_LEAVES}
    carry = (h, *pools, state["ssm"], state["conv"])
    experts, counts = [], 0

    for kind, first, first_of_kind, length in layer_runs(cfg):
        own = params[kind]

        def body(carry, idx, kind=kind, own=own):
            h, k_pool, v_pool, ssm, conv = carry
            layer, i = idx
            lp = {**_at(every, layer), **_at(own, i)}
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
            if kind == "mamba":
                h, ssm, conv = mix(lp, i, h, x, ssm, conv)
            else:
                h, k_pool, v_pool = attend(lp, i, h, x, k_pool, v_pool)
            h, top_idx, n = _moe_residual(lp, moe, layer, h, cfg)
            return (h, k_pool, v_pool, ssm, conv), (top_idx, n)

        carry, (chosen, n) = jax.lax.scan(
            body, carry,
            (jnp.arange(first, first + length, dtype=jnp.int32),
             jnp.arange(first_of_kind, first_of_kind + length,
                        dtype=jnp.int32)))
        experts.append(chosen)
        counts = counts + jnp.sum(n, axis=0)

    h, k_pool, v_pool, ssm, conv = carry
    aux = {"experts": jnp.concatenate(experts),
           **{name: counts[i] for i, name in enumerate(STEP_COUNTERS)}}
    return h, (k_pool, v_pool), {"ssm": ssm, "conv": conv}, aux


# ------------------------------------------------------------------ forwards
def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1]
    pools: PagedPools,         # [kv_layers, N, page, Hkv*D] each
    page_table: jnp.ndarray,   # [B, Pmax]
    lengths: jnp.ndarray,      # [B] valid length BEFORE this token
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,
    mesh: Any = None,
    *,
    state: State,              # {"ssm", "conv"}: [state_layers, rows, ...]
    run_layers: Callable | None = None,
    mixer: Mixer = MAMBA2,
) -> tuple[jnp.ndarray, PagedPools, State, Aux]:
    """One decode step over the page pool and the state slab. Returns
    (hidden [B, 1, H], pools, state, aux); pages and state move as in
    ``falcon_h1.forward_paged_decode``, each in the layers of its kind.
    ``run_layers``: another stack over the same two sub-layers (``_run_layers``
    says what it is handed; ``models/nemotron_h.py``). ``mixer``: what a
    layer that holds state computes (``Mixer``)."""
    interpret = _one_device(mesh, interpret, cfg)
    cos_t, sin_t = rope_tables     # read only where cfg.rotary
    B = input_ids.shape[0]
    positions = lengths[None, :]
    if write_mask is None:
        write_mask = jnp.ones((B,), bool)
    pid, off = _decode_targets(page_table, lengths, write_mask,
                               pools[0].shape[2])
    decode_attend = _decode_attend(cfg, interpret, None)
    work = decode_work(page_table, lengths + 1)
    h = _embed_scale(embed_lookup(params["embed"], input_ids.reshape(1, B),
                                  params["final_norm"].dtype), cfg)

    def mix(lp, i, h, x, ssm, conv):
        z, u, dt = mixer.into(lp, x, cfg)
        y, ssm, conv = mixer.step(lp, i, cfg, u[0], dt[0], ssm, conv,
                                  write_mask, h.dtype, not interpret)
        return (_branch(h, mixer.out(lp, y[None], z, cfg, h.dtype), cfg),
                ssm, conv)

    def attend(lp, i, h, x, k_pool, v_pool):
        q, kproj, vproj = _qkv_proj(lp, x, cfg, positions, cos_t, sin_t)
        k_pool = k_pool.at[i, pid, off].set(
            kproj.reshape(B, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[i, pid, off].set(
            vproj.reshape(B, -1).astype(v_pool.dtype))
        attn = decode_attend(q[0], k_pool, v_pool, work, i)
        return (_attn_out(lp, h, _gated(lp, x, attn.reshape(1, B, -1)),
                          cfg.residual_multiplier), k_pool, v_pool)

    h, pools, state, aux = (run_layers or _run_layers)(
        params, cfg, h, pools, state, mix, attend)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h.reshape(B, 1, -1), pools, state, aux


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc]
    pools: PagedPools,
    page_table: jnp.ndarray,   # [B, Pmax]
    hist: jnp.ndarray,         # [R] tokens BEFORE each lane's span
    q_lens: jnp.ndarray,       # [R] span length (0 = idle lane)
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,
    mesh: Any = None,
    *,
    rows: jnp.ndarray | None = None,
    decode: DecodeGroup | None = None,
    state: State,
    run_layers: Callable | None = None,
    mixer: Mixer = MAMBA2,
) -> tuple[jnp.ndarray, PagedPools, State, Aux]:
    """One ragged mixed step over the tokens it has. Returns (hidden, pools,
    state, aux); lanes, the decode group, pages, state and ``hidden`` as
    ``falcon_h1.forward_paged_mixed``, each cache in the layers of its
    kind. ``run_layers`` and ``mixer`` as in ``forward_paged_decode``."""
    interpret = _one_device(mesh, interpret, cfg)
    cos_t, sin_t = rope_tables     # read only where cfg.rotary
    R, Qc = input_ids.shape
    lay = mixed_layout(input_ids, page_table, hist, q_lens, write_mask, rows,
                       decode, pools[0])
    nd = lay.n_dec
    lane_attend = _ragged_attend(cfg, interpret, None)
    decode_attend = _decode_attend(cfg, interpret, None)
    advance = lay.lane_valid                     # lanes whose state moves
    span = jnp.where(advance, q_lens, 0)
    h = _embed_scale(embed_lookup(params["embed"], lay.ids,
                                  params["final_norm"].dtype), cfg)

    def mix(lp, i, h, x, ssm, conv):
        # one input and one output projection over all tokens, split only
        # around the recurrence: the decode group's step, the lanes' chunk
        z, u, dt = mixer.into(lp, x, cfg)
        ys = []
        if nd:
            y_dec, ssm, conv = mixer.step(
                lp, i, cfg, u[0, :nd], dt[0, :nd], ssm, conv, decode.run,
                h.dtype, not interpret)
            ys.append(y_dec)
        y, ssm, conv = mixer.chunk(
            lp, i, cfg, u[0, nd:].reshape(R, Qc, -1),
            dt[0, nd:].reshape(R, Qc, -1), ssm, conv, rows, hist == 0,
            advance, span, h.dtype)
        ys.append(y.reshape(R * Qc, -1))
        m = mixer.out(lp, jnp.concatenate(ys)[None], z, cfg, h.dtype)
        return _branch(h, m, cfg), ssm, conv

    def attend(lp, i, h, x, k_pool, v_pool):
        q, kproj, vproj = _qkv_proj(lp, x, cfg, lay.positions, cos_t, sin_t)
        n = lay.pid.shape[0]
        k_pool = k_pool.at[i, lay.pid, lay.off].set(
            kproj.reshape(n, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[i, lay.pid, lay.off].set(
            vproj.reshape(n, -1).astype(v_pool.dtype))
        attn = mixed_attention(lay, q, k_pool, v_pool, hist, q_lens, i,
                               lane_attend, decode_attend)
        return (_attn_out(lp, h, _gated(lp, x, attn),
                          cfg.residual_multiplier), k_pool, v_pool)

    h, pools, state, aux = (run_layers or _run_layers)(
        params, cfg, h, pools, state, mix, attend)
    h = mixed_hidden_out(lay, h, q_lens, rows)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, pools, state, aux
