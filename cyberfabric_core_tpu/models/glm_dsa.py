"""GLM-5 (``model_type: glm_moe_dsa``): kimi_k2's block with a learned
indexer in front of the attention. Written from the published config and the
family's published indexer (DeepSeek-V3.2-Exp's); pre-norm residual, RMSNorm,
no bias anywhere but the index key's LayerNorm.

A layer is ``models/kimi_k2.py``'s (latent attention over a latent page,
absorbed; leading dense layers, then sigmoid-routed experts beside a shared
expert; a chip's share of the experts and of the vocabulary), and its
projections, its expert layer and its layer scan are kimi_k2's functions,
called. What is new sits between the projections and the attention:

    qI_i = c_q W_qI,i  [index_head_dim],  i < index_heads;  kI = LayerNorm(x W_kI)
    the first qk_rope_head_dim numbers of qI_i and kI rotated (the layer's tables)
    w    = (x W_w) index_heads^-1/2 index_head_dim^-1/2                  float32
    I(t, s) = sum_i w_i(t) relu(qI_i(t) . kI(s)),  s <= t                float32
    S_t  = the index_topk keys s <= t of largest I(t, s) (every one while
           t + 1 <= index_topk);  attention over S_t only

**The cache is a latent chain of two arrays** (``runtime/paged.py``):
``pools = (latent_pool, index_pool)``, ``kI`` at the page and offset of the
token's latent row, ``cfg.index_lanes`` wide (the published 128 is one lane
tile). The latent row is NOT widened to hold it: the index pass reads 256 B
a key, not the row's 1 536.

**The three steps of a decode row** (``ops/dsa.py``): the index pass over
the row's index pages (``dsa_index_scores``), the exact selection
(``dsa_select`` inside the scope ``dsa_topk``: the ``index_topk``-th score
and its tie's position by bisection, no sort; then the mask), and the latent
decode kernel over the row's span under the keys the query chose
(``dsa_sparse_decode_attention``: it attends the chosen rows and no others,
and reads every page of the span). **A chunk** is the same three steps over
its queries: ``dsa_index_scores_ragged``, each query's selection, and the
ragged latent kernel under the keys each query chose
(``dsa_ragged_attention``). One operand, ``keep``, is all either kernel
gains.

Every entry point also returns ``aux``: kimi_k2's (the experts chosen and
its ``STEP_COUNTERS``), the keys each query chose (``aux["chosen"]`` [L, N,
index_topk], positions rising, -1 past the count: what the benchmark's judge
attends over and holds against the reference's own scores; made from the
mask, and not computed by a program that does not return it: the served
steps read the counters alone) and this module's counters, summed over the
layers: the keys the index passes scored, the keys attended, the queries and
those of them that saw more than ``index_topk`` keys.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..ops import dsa
from ..ops.norms import layer_norm, rms_norm
from ..ops.rope import apply_rope, attention_scale
from . import kimi_k2
from .configs import ModelConfig
from .kimi_k2 import (_proj, _run_layers, attention_out, compressed_query,
                      latent_and_query)
from .llama import (DecodeGroup, Params, _decode_targets, decode_work,
                    embed_lookup, gather_last_hidden, lm_head_logits,
                    mixed_hidden_out, mixed_layout)

__all__ = ["init_params", "init_params_with", "forward_paged_decode",
           "forward_paged_mixed", "lm_head_logits", "gather_last_hidden",
           "STEP_COUNTERS"]

#: kimi_k2's, then what the index passes and the attention behind them
#: counted over a forward's layers: keys scored (a query's visible keys),
#: keys attended, queries, and the queries that saw more than ``index_topk``
DSA_COUNTERS = ("keys_scored", "keys_selected", "queries", "queries_binding")
STEP_COUNTERS = kimi_k2.STEP_COUNTERS + DSA_COUNTERS

#: the index key's LayerNorm (the published indexer's: with a bias)
INDEX_NORM_EPS = 1e-6

Pools = tuple[jnp.ndarray, jnp.ndarray]     # (latent, index)
Aux = dict[str, jnp.ndarray]


def _one_device(mesh: Any, interpret: bool | None) -> bool:
    return kimi_k2._one_device(mesh, interpret, "glm_moe_dsa")


# ---------------------------------------------------------------- parameters
def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """kimi_k2's tree (``kimi_k2.init_params_with``'s contract) with the
    indexer's leaves in both stacks: ``index_wq``, ``index_wk`` (matrices),
    the index key's LayerNorm ``index_k_norm`` and ``index_k_bias``, and
    ``index_w`` float32 at ``fan_in^-1/2`` like the router (a head's weight
    decides WHICH keys are attended, and weighs in with either sign)."""
    params = kimi_k2.init_params_with(cfg, key, dtype, matmul, embed)
    shapes, Di = cfg.latent_matrices(), cfg.index_head_dim
    keys = iter(jax.random.split(jax.random.fold_in(key, 1), 8))
    for stack, n in (("dense", cfg.first_k_dense),
                     ("layers", cfg.num_moe_layers)):
        params[stack].update({
            "index_wq": matmul(next(keys), (n, *shapes["index_wq"])),
            "index_wk": matmul(next(keys), (n, *shapes["index_wk"])),
            "index_k_norm": jnp.ones((n, Di), dtype),
            "index_k_bias": jnp.zeros((n, Di), dtype),
            "index_w": jax.random.normal(
                next(keys), (n, *shapes["index_w"]), jnp.float32
            ) * cfg.hidden_size ** -0.5})
    return params


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Random-init parameters at model shape, every matrix at
    ``fan_in^-1/2``."""
    def matmul(k, shape):
        return jax.random.normal(k, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)

    return init_params_with(cfg, key, dtype, matmul, matmul)


# ----------------------------------------------------------------- the indexer
def index_inputs(lp: dict, x: jnp.ndarray, c_q: jnp.ndarray,
                 cfg: ModelConfig, positions, cos_t, sin_t):
    """One layer's indexer from normed ``x`` [1, N, H] and ``c_q``: the
    index keys to cache ``[N, index_lanes]``, the index queries ``[N, Hi,
    index_lanes]`` (zeros up to whole lane tiles in both) and the heads'
    weights ``[N, Hi]`` float32."""
    N = x.shape[1]
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    q = _proj(c_q, lp["index_wq"]).astype(x.dtype).reshape(1, N, Hi, Di)
    k = layer_norm(_proj(x, lp["index_wk"]).astype(x.dtype),
                   lp["index_k_norm"], lp["index_k_bias"], INDEX_NORM_EPS)
    # tables of qk_rope_head_dim / 2 columns: the leading part is rotated
    q = apply_rope(q, positions, cos_t, sin_t)
    k = apply_rope(k[:, :, None, :], positions, cos_t, sin_t)[:, :, 0]
    w = jnp.einsum("bnh,hi->bni", x.astype(jnp.float32), lp["index_w"],
                   precision=jax.lax.Precision.HIGHEST
                   ) * (Hi * Di) ** -0.5
    pad = cfg.index_lanes - Di
    return (jnp.pad(k[0], ((0, 0), (0, pad))),
            jnp.pad(q[0], ((0, 0), (0, 0), (0, pad))), w[0])


def _layer_inputs(lp, h, cfg, positions, cos_t, sin_t):
    """(latent rows, absorbed queries, index keys, index queries, weights)
    of one layer over ``h`` [1, N, H]."""
    x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
    c_q = compressed_query(lp, x, cfg)
    latent, q = latent_and_query(lp, x, cfg, positions, cos_t, sin_t, c_q)
    return (latent, q, *index_inputs(lp, x, c_q, cfg, positions, cos_t,
                                     sin_t))


def _dsa_counts(cfg: ModelConfig, seen: jnp.ndarray) -> jnp.ndarray:
    """``DSA_COUNTERS`` of a forward whose queries see ``seen`` [n] keys each
    (0: a query that did not run), over its layers."""
    topk = cfg.index_topk
    return cfg.num_layers * jnp.stack([
        jnp.sum(seen), jnp.sum(jnp.minimum(seen, topk)),
        jnp.sum(seen > 0), jnp.sum(seen > topk)]).astype(jnp.int32)


def _finish(aux: Aux, chosen: jnp.ndarray, counts: jnp.ndarray) -> Aux:
    return {**aux, "chosen": chosen,
            **{n: counts[i] for i, n in enumerate(DSA_COUNTERS)}}


def _chosen_keys(cfg: ModelConfig, scores: jnp.ndarray, span,
                 interpret: bool) -> jnp.ndarray:
    """``[.., S]`` int8: the ``index_topk`` keys of largest ``scores`` a
    query (every key it sees while they are fewer), none at or past
    ``span``."""
    _, kth, kth_at = dsa.select(scores, cfg.index_topk, span,
                                interpret=interpret)
    return dsa.keep_mask(scores, kth, kth_at)


def _decode_attend(cfg, q, qi, w, pools, table, lengths, layer, scale,
                   interpret):
    """The three steps of decode rows ``q`` [B, Hq, lanes] of ``lengths``
    (the step's token counted): (o~ [B, Hq, rank], the positions chosen)."""
    latent_pool, index_pool = pools
    from ..ops.mla_attention import mla_decode_attention

    scores = dsa.index_scores(qi, w, index_pool, table, lengths, layer,
                              interpret=interpret)
    keep = _chosen_keys(cfg, scores, jnp.max(lengths), interpret)
    o = mla_decode_attention(
        q, latent_pool, table, lengths, layer, rank=cfg.kv_lora_rank,
        scale=scale, interpret=interpret, keep=keep,
        name="dsa_sparse_decode_attention")
    return o, dsa.chosen_positions(keep, cfg.index_topk)


# ------------------------------------------------------------------ forwards
def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1] one token a slot
    pools: Pools,
    page_table: jnp.ndarray,   # [B, Pmax]
    lengths: jnp.ndarray,      # [B] valid length BEFORE this token
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,   # [B]; False rows -> scratch
    mesh: Any = None,
) -> tuple[jnp.ndarray, Pools, Aux]:
    """One decode step over the latent pool and the index pool. Returns
    (hidden [B, 1, H], pools, aux)."""
    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    B = input_ids.shape[0]
    page_size = pools[0].shape[2]
    positions = lengths[None, :]
    pid, off = _decode_targets(page_table, lengths, write_mask, page_size)
    table, now = decode_work(page_table, lengths + 1)
    scale = attention_scale(cfg)
    h = embed_lookup(params["embed"], input_ids.reshape(1, B),
                     params["final_norm"].dtype)

    def attend(lp, layer, h, carry):
        (latent_pool, index_pool), chosen = carry
        with jax.named_scope("glm_dsa_layer"):
            latent, q, ki, qi, w = _layer_inputs(lp, h, cfg, positions,
                                                 cos_t, sin_t)
            pools = (latent_pool.at[layer, pid, off].set(
                         latent.astype(latent_pool.dtype)),
                     index_pool.at[layer, pid, off].set(
                         ki.astype(index_pool.dtype)))
            o, picked = _decode_attend(cfg, q, qi, w, pools, table, now,
                                       layer, scale, interpret)
        return attention_out(lp, h, o, cfg), (pools,
                                              chosen.at[layer].set(picked))

    chosen = jnp.full((cfg.num_layers, B, cfg.index_topk), -1, jnp.int32)
    h, (pools, chosen), aux = _run_layers(params, cfg, h,
                                          (tuple(pools), chosen), attend)
    ran = jnp.ones((B,), bool) if write_mask is None else write_mask
    aux = _finish(aux, chosen, _dsa_counts(cfg, jnp.where(ran, now, 0)))
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h.reshape(B, 1, -1), pools, aux


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc] per-lane query span, padded
    pools: Pools,
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
) -> tuple[jnp.ndarray, Pools, Aux]:
    """One mixed step over the tokens it has (``kimi_k2.forward_paged_mixed``
    over both arrays): the lanes' spans through the ragged index pass and
    the ragged latent kernel under the keys each query chose, the decode
    group through the three steps of a decode row. Returns (hidden, pools,
    aux)."""
    from ..ops.mla_attention import mla_ragged_attention

    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    R, Qc = input_ids.shape
    lay = mixed_layout(input_ids, page_table, hist, q_lens, write_mask, rows,
                       decode, pools[0])
    nd = lay.n_dec
    rank, scale, topk = cfg.kv_lora_rank, attention_scale(cfg), cfg.index_topk
    h = embed_lookup(params["embed"], lay.ids, params["final_norm"].dtype)

    def attend(lp, layer, h, carry):
        (latent_pool, index_pool), chosen = carry
        with jax.named_scope("glm_dsa_layer"):
            latent, q, ki, qi, w = _layer_inputs(lp, h, cfg, lay.positions,
                                                 cos_t, sin_t)
            # the step's rows go in BEFORE it scores and attends: a chunk
            # reads its own earlier tokens back through the page chain
            pools = (latent_pool.at[layer, lay.pid, lay.off].set(
                         latent.astype(latent_pool.dtype)),
                     index_pool.at[layer, lay.pid, lay.off].set(
                         ki.astype(index_pool.dtype)))
            scores = dsa.index_scores_ragged(
                qi[nd:].reshape(R, Qc, *qi.shape[1:]),
                w[nd:].reshape(R, Qc, -1), pools[1], lay.lane_table, hist,
                q_lens, layer, interpret=interpret)
            keep = _chosen_keys(
                cfg, scores, jnp.max(jnp.where(q_lens > 0, hist + q_lens, 0)),
                interpret)
            lane_q = q[nd:].reshape(R, Qc, *q.shape[1:]).transpose(0, 2, 1, 3)
            lane = mla_ragged_attention(
                lane_q, pools[0], lay.lane_table, hist, q_lens, layer,
                rank=rank, scale=scale, interpret=interpret, keep=keep,
                name="dsa_ragged_attention")
            o = lane.transpose(0, 2, 1, 3).reshape(R * Qc, -1, rank)
            picked = dsa.chosen_positions(keep, topk).reshape(R * Qc, -1)
            if nd:
                dec, dec_picked = _decode_attend(
                    cfg, q[:nd], qi[:nd], w[:nd], pools, *lay.work, layer,
                    scale, interpret)
                o = jnp.concatenate([dec, o], axis=0)
                picked = jnp.concatenate([dec_picked, picked], axis=0)
        return attention_out(lp, h, o, cfg), (pools,
                                              chosen.at[layer].set(picked))

    chosen = jnp.full((cfg.num_layers, lay.ids.shape[1], topk), -1,
                      jnp.int32)
    h, (pools, chosen), aux = _run_layers(params, cfg, h,
                                          (tuple(pools), chosen), attend)
    # a lane's query at ``hist + qi`` sees that many keys and itself
    offs = jnp.arange(Qc, dtype=jnp.int32)[None, :]
    seen = jnp.where(offs < q_lens[:, None], hist[:, None] + offs + 1, 0)
    if write_mask is not None:
        seen = jnp.where(write_mask[:, None], seen, 0)
    seen = seen.reshape(-1)
    if nd:
        seen = jnp.concatenate(
            [jnp.where(decode.run, decode.lengths + 1, 0), seen])
    aux = _finish(aux, chosen, _dsa_counts(cfg, seen))
    h = mixed_hidden_out(lay, h, q_lens, rows)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, pools, aux
