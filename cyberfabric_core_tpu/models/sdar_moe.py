"""SDAR-MoE: a decoder of routed experts that generates by diffusion over
blocks (``model_type: sdar_moe``; written from the published config and the
model card's description of generation).

    h  <- h + W_o · Attn(RoPE(RMSNorm_D(W_q x) g_q), RoPE(RMSNorm_D(W_k x) g_k),
              W_v x),                          x = RMSNorm(h)
    h  <- h + sum_{e in top8} g_e · W_down,e(SiLU(W_gate,e x') * W_up,e x'),
              g = softmax over the chosen of softmax_f32(x' W_r),  x' = RMSNorm(h)

**The mask.** The sequence is cut into blocks of ``cfg.block_length`` at
absolute positions; key ``j`` is visible to query ``i`` iff ``j``'s block is
not after ``i``'s: causal between blocks, full inside one. The logits at
position ``i`` are for the token AT ``i`` (a masked position predicts itself).

**The forwards**, by the names ``runtime/scheduler.py`` drives for every
decoder. A prompt's WHOLE blocks go through the lane of
``forward_paged_mixed`` and their K/V is kept. A running row's OPEN block
(the prompt's leftover, then ``mask_token_id``) is the ``[B, W]`` operand
where the llama family has one token: ``forward_paged_decode`` and the decode
group of a mixed step run its ``W`` positions at ``lengths .. lengths+W-1``
against the kept K/V and the block itself, through the decode kernel with the
block folded into the GQA group axis. Every such forward WRITES the block's
K/V there. A *denoise* forward's K/V never outlives it: the next forward of
the row, denoise or commit, starts at the same ``lengths`` and scatters before
it attends (rewrite-before-read, as a rejected draft's suffix). A *commit*
forward is the same call on a block with no mask left, after which the caller
advances ``lengths`` by ``W``. Which of the two a forward was is the caller's
bookkeeping, not the model's.

Every entry point also returns ``aux``: the experts each token chose
(``[L, N, K]``, what the benchmark's judge holds against the reference's own
scores) and ``touched``, the experts with at least one token summed over the
layers (``STEP_COUNTERS``: the ``/metrics`` counter behind
``moe_experts_touched_share``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.platform import default_interpret as _default_interpret
from .configs import ModelConfig
from .llama import (DecodeGroup, PagedPools, Params, _attn_out,
                    _decode_targets, _qkv_proj, _ragged_attend, decode_work,
                    embed_lookup, gather_last_hidden, init_params,
                    lm_head_logits, mixed_attention, mixed_layout,
                    moe_experts, moe_item_rows, moe_route, split_moe)

__all__ = ["init_params", "forward_paged_decode", "forward_paged_mixed",
           "lm_head_logits", "gather_last_hidden", "STEP_COUNTERS"]

Aux = dict[str, jnp.ndarray]
#: what ``aux`` counts over a forward's expert layers, for the serving
#: programs to hand to the host: the experts with at least one token, and the
#: rows one grouped matmul of the layer multiplied (``llama.moe_item_rows``)
STEP_COUNTERS = ("touched", "item_rows")


def _one_device(mesh: Any, interpret: bool | None) -> bool:
    if mesh is not None:
        raise ValueError("sdar_moe serves on one device: the grouped expert "
                         "matmul has no tp or ep partitioning")
    return _default_interpret() if interpret is None else interpret


def _block_attend(interpret: bool, width: int):
    """``attend(q [B*W, Hq, D], k_pool, v_pool, work, layer)`` for
    ``mixed_attention``'s decode group: the open blocks."""
    from ..ops.paged_attention import paged_block_attention

    def attend(qq, kk, vv, work, ly):
        out = paged_block_attention(
            qq.reshape(-1, width, *qq.shape[1:]), kk, vv, *work, ly,
            interpret=interpret)
        return out.reshape(qq.shape)

    return attend


def _moe_residual(lp: dict, moe: dict, layer, h: jnp.ndarray,
                  cfg: ModelConfig):
    """Post-attention norm + the expert layer + residual over ``h``
    [1, N, H]; also the experts chosen [N, K] and the layer's
    ``STEP_COUNTERS``."""
    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    flat = x.reshape(-1, x.shape[-1])
    top_idx, gates = moe_route(flat, lp["router"], cfg.experts_per_token)
    y = moe_experts(flat, top_idx, gates, moe, cfg, layer)
    touched = jnp.sum(jnp.bincount(top_idx.reshape(-1),
                                   length=cfg.num_experts) > 0)
    counts = jnp.stack([touched.astype(jnp.int32),
                        moe_item_rows(top_idx, cfg)])
    return h + y.reshape(h.shape).astype(h.dtype), top_idx, counts


def _run_layers(params: Params, cfg: ModelConfig, h, pools, body):
    """Scan ``body(lp, layer, h, k_pool, v_pool) -> (attention output, pools)``
    then the expert layer over the blocks; returns (h, pools, aux)."""
    scanned, moe = split_moe(params["layers"])

    def layer_body(carry, xs):
        h, k_pool, v_pool = carry
        lp, layer = xs
        h, k_pool, v_pool = body(lp, layer, h, k_pool, v_pool)
        h, top_idx, counts = _moe_residual(lp, moe, layer, h, cfg)
        return (h, k_pool, v_pool), (top_idx, counts)

    (h, k_pool, v_pool), (experts, counts) = jax.lax.scan(
        layer_body, (h, *pools),
        (scanned, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    counts = jnp.sum(counts, axis=0)
    return h, (k_pool, v_pool), {
        "experts": experts,
        **{n: counts[i] for i, n in enumerate(STEP_COUNTERS)}}


def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, W] each slot's open block
    pools: PagedPools,
    page_table: jnp.ndarray,   # [B, Pmax]
    lengths: jnp.ndarray,      # [B] kept K/V length: where the block starts
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,   # [B]; False rows -> scratch
    mesh: Any = None,
) -> tuple[jnp.ndarray, PagedPools, Aux]:
    """One forward of every slot's open block. Returns (hidden [B, W, H],
    pools, aux); the block's K/V is written at ``lengths ..`` (see the
    module's note on what keeps it)."""
    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    B, W = input_ids.shape
    Hq, D = cfg.num_heads, cfg.head_dim
    positions = lengths[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    pid, off = _decode_targets(page_table, lengths, write_mask,
                               pools[0].shape[2], W)
    pid, off = pid.reshape(-1), off.reshape(-1)
    attend = _block_attend(interpret, W)
    work = decode_work(page_table, lengths + W)
    h = embed_lookup(params["embed"], input_ids.reshape(1, B * W),
                     params["final_norm"].dtype)

    def body(lp, layer, h, k_pool, v_pool):
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q, kproj, vproj = _qkv_proj(lp, x, cfg, positions.reshape(1, -1),
                                    cos_t, sin_t)
        k_pool = k_pool.at[layer, pid, off].set(
            kproj.reshape(B * W, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, pid, off].set(
            vproj.reshape(B * W, -1).astype(v_pool.dtype))
        attn = attend(q[0], k_pool, v_pool, work, layer)
        return (_attn_out(lp, h, attn.reshape(1, B * W, Hq * D)),
                k_pool, v_pool)

    h, pools, aux = _run_layers(params, cfg, h, pools, body)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h.reshape(B, W, -1), pools, aux


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc] whole blocks of a prompt, padded
    pools: PagedPools,
    page_table: jnp.ndarray,   # [B, Pmax]
    hist: jnp.ndarray,         # [R] kept tokens BEFORE each lane's span
    q_lens: jnp.ndarray,       # [R] span length (0 = idle lane)
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,
    mesh: Any = None,
    *,
    rows: jnp.ndarray | None = None,
    decode: DecodeGroup | None = None,
) -> tuple[jnp.ndarray, PagedPools, Aux]:
    """One mixed step over the tokens it has: the lanes' spans (a prompt's
    whole blocks, each starting on a block boundary; lanes, ``rows`` and
    ``write_mask`` as ``llama.forward_paged_mixed``) under the block mask,
    and with ``decode`` every slot's open block ``[B, W]`` beside them, in one
    pass over the weights. Returns (hidden, pools, aux): ``[B, W, H]``, the
    open blocks' hidden, with a decode group (a lane has no first token to
    sample: the prompt's leftover opens the row's first block), else the
    lanes' ``[R, Qc, H]``."""
    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    R, Qc = input_ids.shape
    lay = mixed_layout(input_ids, page_table, hist, q_lens, write_mask, rows,
                       decode, pools[0])
    nd = lay.n_dec
    lane_attend = _ragged_attend(cfg, interpret, None)
    block_attend = _block_attend(interpret, cfg.block_length)
    h = embed_lookup(params["embed"], lay.ids, params["final_norm"].dtype)

    def body(lp, layer, h, k_pool, v_pool):
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q, kproj, vproj = _qkv_proj(lp, x, cfg, lay.positions, cos_t, sin_t)
        n = lay.pid.shape[0]
        k_pool = k_pool.at[layer, lay.pid, lay.off].set(
            kproj.reshape(n, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, lay.pid, lay.off].set(
            vproj.reshape(n, -1).astype(v_pool.dtype))
        attn = mixed_attention(lay, q, k_pool, v_pool, hist, q_lens, layer,
                               lane_attend, block_attend)
        return _attn_out(lp, h, attn), k_pool, v_pool

    h, pools, aux = _run_layers(params, cfg, h, pools, body)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    if nd:
        return h[0, :nd].reshape(decode.tokens.shape + h.shape[2:]), pools, aux
    return h[0].reshape(R, Qc, -1), pools, aux
