"""Ouro: a llama stack run several times a token (``model_type: ouro``, the
looped language model of arXiv 2510.25741; written from the published config
and the family's modelling code as ISSUE 52 set it down).

    h = E[ids]
    for t in 1..R:                       R = cfg.loop_steps; ONE set of weights
      for l in 1..L:
        h <- h + RMSNorm(W_o Attn_l(RMSNorm(h; g1_l)); g2_l)
        h <- h + RMSNorm(W_down_l(silu(W_gate_l x) * W_up_l x); g4_l),
                                         x = RMSNorm(h; g3_l)
      h <- RMSNorm(h; g_final)           after EVERY pass
      lam_t = sigmoid(w_exit . h + b_exit)
    logits = W_head h                    h of the pass taken

**Where the pass index lives.** Pass ``t`` of layer ``l`` attends over what
pass ``t`` of layer ``l`` wrote for the earlier positions, so a token caches
``R x L`` layers of K and V and the page pool is ``cfg.kv_layers = R x L``
deep: cache layer ``t * L + l``. The weights' stack has ``L`` layers. The two
indices meet in ONE place, ``_run_passes``: an outer ``lax.scan`` over the
passes whose body is the scan over the layers (one compiled layer body, one
compiled pass, whatever ``R`` and ``L`` are), the pools in both carries. The
page table, the write targets and what a decode step's kernel walks are a
step's, the same for all ``R x L`` kernel calls, so they are built once,
outside both.

**The exit.** ``p_t = lam_t prod_{s<t}(1 - lam_s)`` for ``t < R`` and ``p_R``
the rest; a token leaves at the first pass whose cumulated ``p`` reaches
``cfg.early_exit_threshold``. At the published 1 that is pass ``R``, always:
every pass runs and the head reads the last, which is all that is served (a
threshold under 1 is refused at build: rows of one batch at different depths
is a scheduler change). The gate is computed all the same and leaves the
forwards in ``aux``: ``lam`` ``[R, N]`` for every token of the call, and the
two ``STEP_COUNTERS`` over the decode rows that ran: the pass at which the
gate WOULD have let each out, ``sum_t t p_t``, in thousandths, and the rows.

Everything else is ``models/llama.py``'s, called and not copied: ``_qkv_proj``,
the output projection and the MLP (as branches, before the residual), the
layouts, the paged kernels' wrappers, the head.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..ops.attention import attention_with_cache
from ..ops.norms import rms_norm
from ..ops.platform import default_interpret as _default_interpret
from .configs import ModelConfig
from .llama import (DecodeGroup, KVCache, PagedPools, Params, _attn_proj,
                    _decode_attend, _decode_targets, _dense_mlp,
                    _merged_pools, _qkv_proj, _ragged_attend, _restore_pools,
                    decode_work, embed_lookup, gather_last_hidden, init_cache,
                    lm_head_logits, mixed_attention, mixed_hidden_out,
                    mixed_layout)

__all__ = ["init_params", "init_params_with", "init_cache", "forward",
           "forward_paged_decode", "forward_paged_mixed", "lm_head_logits",
           "gather_last_hidden", "exit_pass", "STEP_COUNTERS"]

Aux = dict[str, jnp.ndarray]
#: what ``aux`` counts over a forward's decode rows that ran, for the serving
#: programs to hand to the host on the drained matrix: the expected exit pass
#: (``exit_pass``) summed over them in thousandths of a pass, and the rows
STEP_COUNTERS = ("exit_pass_milli", "exit_rows")


def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """The parameter tree, its matrices made by ``matmul(key, shape)`` (the
    contraction on axis -2) and its embedding by ``embed(key, shape)``
    (``kimi_k2.init_params_with``'s contract). Norms are ones; the exit gate
    float32, never quantized."""
    H, I, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_layers)
    Dq, Dkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 12))
    norms = ("attn_norm", "mlp_norm") + (
        ("attn_post_norm", "mlp_post_norm") if cfg.sandwich_norm else ())
    layers = {name: jnp.ones((L, H), dtype) for name in norms}
    for name, shape in (("wq", (H, Dq)), ("wk", (H, Dkv)), ("wv", (H, Dkv)),
                        ("wo", (Dq, H)), ("gate", (H, I)), ("up", (H, I)),
                        ("down", (I, H))):
        layers[name] = matmul(next(keys), (L, *shape))
    return {"embed": embed(next(keys), (V, H)),
            "final_norm": jnp.ones((H,), dtype),
            "lm_head": matmul(next(keys), (H, V)),
            "exit_gate": {
                "w": jax.random.normal(next(keys), (H,), jnp.float32)
                * H ** -0.5,
                "b": jnp.zeros((), jnp.float32)},
            "layers": layers}


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Random parameters at the model's shapes, in ``dtype``."""
    def matmul(k, shape):
        return jax.random.normal(k, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)

    return init_params_with(cfg, key, dtype, matmul, matmul)


def exit_pass(lam: jnp.ndarray) -> jnp.ndarray:
    """The expected exit pass ``sum_t t p_t`` (1-based) of the gate values
    ``lam`` [R, N]: ``p_t = lam_t prod_{s<t}(1 - lam_s)`` for ``t < R``, and
    ``p_R`` what is left (``lam_R`` decides nothing). Summed as ``sum_t
    P(pass t is reached)``, which is the same number: a token that leaves at
    pass ``T`` has reached passes ``1..T``. [N] f32."""
    reached = jnp.cumprod(1.0 - lam[:-1], axis=0)       # passes 2..R
    return 1.0 + jnp.sum(reached, axis=0)


def _aux(lam: jnp.ndarray, ran: jnp.ndarray | None) -> Aux:
    """``lam`` [R, N]; ``ran`` [n] bool marks the decode rows that ran, the
    call's FIRST ``n`` tokens (None: the call has no decode rows)."""
    if ran is None:
        milli = rows = jnp.zeros((), jnp.int32)
    else:
        passes = exit_pass(lam[:, : ran.shape[0]])
        milli = jnp.sum(jnp.where(ran, jnp.round(1000.0 * passes), 0.0)
                        ).astype(jnp.int32)
        rows = jnp.sum(ran).astype(jnp.int32)
    return {"lam": lam, "exit_pass_milli": milli, "exit_rows": rows}


def _run_passes(params: Params, cfg: ModelConfig, h: jnp.ndarray, caches,
                attend: Callable):
    """``cfg.loop_steps`` passes of the stack over ``h`` [B, T, H].
    ``attend(lp, x, cache_layer, caches) -> (attention [B, T, Hq D],
    caches)`` writes and reads cache layer ``t * L + l``. Returns (the last
    pass's NORMED hidden, caches, lam [R, B T])."""
    L, eps = cfg.num_layers, cfg.rms_norm_eps
    gate = params["exit_gate"]

    def branch(y, lp, name):
        """A branch on its way into the residual: normed again first under
        sandwich norms."""
        if cfg.sandwich_norm:
            y = rms_norm(y, lp[name], eps)
        return y.astype(h.dtype)

    def pass_body(carry, t):
        def layer_body(carry, xs):
            h, caches = carry
            lp, layer = xs
            with jax.named_scope("ouro_layer"):
                x = rms_norm(h, lp["attn_norm"], eps)
                attn, caches = attend(lp, x, t * L + layer, caches)
                h = h + branch(_attn_proj(lp, attn, h.dtype), lp,
                               "attn_post_norm")
                x = rms_norm(h, lp["mlp_norm"], eps)
                h = h + branch(_dense_mlp(lp, x, cfg), lp, "mlp_post_norm")
            return (h, caches), None

        with jax.named_scope("loop_pass"):
            (h, caches), _ = jax.lax.scan(
                layer_body, carry,
                (params["layers"], jnp.arange(L, dtype=jnp.int32)))
            h = rms_norm(h, params["final_norm"], eps)
            lam = jax.nn.sigmoid(
                jnp.einsum("bth,h->bt", h.astype(jnp.float32), gate["w"],
                           precision=jax.lax.Precision.HIGHEST) + gate["b"])
        return (h, caches), lam.reshape(-1)

    (h, caches), lam = jax.lax.scan(
        pass_body, (h, caches),
        jnp.arange(cfg.loop_steps, dtype=jnp.int32))
    return h, caches, lam


def _one_device(mesh: Any, interpret: bool | None) -> bool:
    if mesh is not None:
        raise ValueError("ouro serves on one device: nothing shards its "
                         "sandwich norms or its exit gate")
    return _default_interpret() if interpret is None else interpret


def forward(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, T]
    positions: jnp.ndarray,    # [B, T] absolute positions
    cache: KVCache,            # ``llama.init_cache``: [R x L, B, S, Hkv, D]
    cache_start: jnp.ndarray,  # [B] write offset
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
) -> tuple[jnp.ndarray, KVCache, Aux]:
    """One forward over a dense cache (``llama.forward``'s contract, without
    the flash path). Returns (hidden [B, T, H] of the last pass, normed;
    cache; aux)."""
    cos_t, sin_t = rope_tables
    B, T = input_ids.shape
    kv_len_after = cache_start + T
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    t_idx = cache_start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]

    def attend(lp, x, layer, caches):
        k_cache, v_cache = caches
        q, kproj, vproj = _qkv_proj(lp, x, cfg, positions, cos_t, sin_t)
        k_cache = k_cache.at[layer, b_idx, t_idx].set(
            kproj.astype(k_cache.dtype))
        v_cache = v_cache.at[layer, b_idx, t_idx].set(
            vproj.astype(v_cache.dtype))
        attn = attention_with_cache(q, k_cache[layer], v_cache[layer],
                                    positions, kv_len_after)
        return attn.reshape(B, T, -1), (k_cache, v_cache)

    h = embed_lookup(params["embed"], input_ids, params["final_norm"].dtype)
    h, cache, lam = _run_passes(params, cfg, h, cache, attend)
    return h, cache, _aux(lam, None)


def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1] one token per slot
    pools: PagedPools,         # (k, v): [R x L, N, page, Hkv D]
    page_table: jnp.ndarray,   # [B, Pmax]
    lengths: jnp.ndarray,      # [B] valid length BEFORE this token
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,   # [B]; False rows -> scratch
    mesh: Any = None,
) -> tuple[jnp.ndarray, PagedPools, Aux]:
    """One decode step (``llama.forward_paged_decode``'s contract): every
    slot's token through all passes, its K/V written to every cache layer.
    Returns (hidden [B, 1, H], pools, aux)."""
    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    B = input_ids.shape[0]
    pools, caller_shape = _merged_pools(pools)
    positions = lengths[:, None]
    pid, off = _decode_targets(page_table, lengths, write_mask,
                               pools[0].shape[2])
    kernel = _decode_attend(cfg, interpret, None)
    # the step's, not a layer's: one list serves all R x L kernel calls
    work = decode_work(page_table, lengths + 1)

    def attend(lp, x, layer, caches):
        k_pool, v_pool = caches
        q, kproj, vproj = _qkv_proj(lp, x, cfg, positions, cos_t, sin_t)
        k_pool = k_pool.at[layer, pid, off].set(
            kproj.reshape(B, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, pid, off].set(
            vproj.reshape(B, -1).astype(v_pool.dtype))
        attn = kernel(q[:, 0], k_pool, v_pool, work, layer)
        return attn.reshape(B, 1, -1), (k_pool, v_pool)

    h = embed_lookup(params["embed"], input_ids, params["final_norm"].dtype)
    h, pools, lam = _run_passes(params, cfg, h, pools, attend)
    ran = jnp.ones((B,), bool) if write_mask is None else write_mask
    return h, _restore_pools(pools, caller_shape), _aux(lam, ran)


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc] per-lane query span, padded
    pools: PagedPools,
    page_table: jnp.ndarray,   # [B, Pmax]
    hist: jnp.ndarray,         # [R] kv tokens BEFORE each lane's span
    q_lens: jnp.ndarray,       # [R] span length (0 = idle lane)
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,
    mesh: Any = None,
    *,
    rows: jnp.ndarray | None = None,
    decode: DecodeGroup | None = None,
) -> tuple[jnp.ndarray, PagedPools, Aux]:
    """One mixed step over the tokens it has (lanes, ``rows``, ``write_mask``
    and the decode group as ``llama.forward_paged_mixed``), through all
    passes. Returns (hidden, pools, aux): the lanes' ``[R, Qc, H]`` without
    a decode group, the ``[B, H]`` rows the head needs with one."""
    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    pools, caller_shape = _merged_pools(pools)
    lay = mixed_layout(input_ids, page_table, hist, q_lens, write_mask, rows,
                       decode, pools[0])
    lane_attend = _ragged_attend(cfg, interpret, None)
    decode_attend = _decode_attend(cfg, interpret, None)
    n = lay.pid.shape[0]

    def attend(lp, x, layer, caches):
        k_pool, v_pool = caches
        q, kproj, vproj = _qkv_proj(lp, x, cfg, lay.positions, cos_t, sin_t)
        # the step's k/v BEFORE attending, as llama's mixed step
        k_pool = k_pool.at[layer, lay.pid, lay.off].set(
            kproj.reshape(n, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, lay.pid, lay.off].set(
            vproj.reshape(n, -1).astype(v_pool.dtype))
        attn = mixed_attention(lay, q, k_pool, v_pool, hist, q_lens, layer,
                               lane_attend, decode_attend)
        return attn, (k_pool, v_pool)

    h = embed_lookup(params["embed"], lay.ids, params["final_norm"].dtype)
    h, pools, lam = _run_passes(params, cfg, h, pools, attend)
    h = mixed_hidden_out(lay, h, q_lens, rows)
    return (h, _restore_pools(pools, caller_shape),
            _aux(lam, None if decode is None else decode.run))
