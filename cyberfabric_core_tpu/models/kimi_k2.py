"""Kimi-K2 (``model_type: kimi_k2``, the DeepSeek-V3 block): latent attention
over a latent page, a leading dense layer, then sigmoid-routed experts beside
a shared expert. Written from the published config; pre-norm residual,
RMSNorm, no bias anywhere.

    c_q = RMSNorm(x W_dq);  q_h = c_q W_uq,h = [q_nope_h | q_rope_h]
    [c_kv | k_r] = x W_dkv;  c = RMSNorm(c_kv);  k_r, q_rope <- RoPE (YaRN)
    [k_nope_h | v_h] = c W_ukv,h
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_r(s)) sigma
    h <- h + concat_h(sum_s p v_h(s)) W_o

**The cache is ``(c, k_r)``**: ``cfg.latent_width`` numbers a token a layer,
ONE pool array ``[L, P, page, cfg.latent_lanes]`` (576 numbers in 640 lanes,
what a TPU's lane tiles make of 576 anyway) with no kv-head axis and no V
pool (``runtime/paged.py``). **Attention runs absorbed**, in decode and in a
prompt's chunk alike: ``q~_h = q_nope_h W_uk,h^T`` (so ``score = (q~_h.c +
q_rope_h.k_r) sigma``), the kernel returns ``o~_h = sum_s p c(s)`` and ``o_h =
o~_h W_uv,h``. ``W_uk`` and ``W_uv`` are the two halves of the ONE stored
``wkv_b`` (int8: its own per-channel scales, applied to ``q_nope`` going in
and to ``o_h`` coming out), never a second quantisation. Per query and key
the absorbed form costs 64 x 2 x (576 + 512) FLOPs where expanded K and V
cost 64 x 2 x (192 + 128), but expanding means gathering a row's whole
latent history and multiplying it by ``W_ukv`` again every chunk (3072 x 512
x 16384 a layer: as many FLOPs as the absorbed chunk itself) and a K/V
temporary of 0.5 MB a token; absorbed, a chunk reads the pages the decode
kernel reads and the pool stays the only cache.

    layer 0:       h <- h + SwiGLU_18432(RMSNorm(h))
    layers 1..:    s = sigmoid(x W_g) in float32; the K largest of s + b;
                   g_e = gamma s_e / sum_chosen s
                   h <- h + SwiGLU_shared(x) + sum_{e chosen, held} g_e SwiGLU_e(x)

**A chip's share** (``cfg.experts_held``, ``cfg.vocab_held``): the router
scores all ``cfg.num_experts``, the gates are normalised over all the chosen,
and the sum runs over the chosen experts this chip holds; the shared expert
is whole; the embedding and the head are the held rows of the vocabulary.
Nothing here stands in for the chips that hold the rest, and the expert
layer does not pay for their assignments either: as a deployment's exchange
hands a chip only the tokens routed to it, ``llama.moe_experts`` gathers,
multiplies and scatter-adds the compacted list of the assignments held here
(``llama.moe_capacity`` rows from shapes, four times the uniform expectation:
640 of a 512-token mixed step's 4 608, 128 of a decode step's 512), chosen
on the device by the held count; a step that holds more takes every row, so
none is dropped (``STEP_COUNTERS``' ``compact`` over ``forwards``).

The stack is not one repeated layer, so the parameters are two stacks:
``params["dense"]`` (the leading ``cfg.first_k_dense`` layers) and
``params["layers"]`` (the expert layers), each scanned; pool layer ``l`` is
model layer ``l``. Every entry point also returns ``aux``: the experts each
token chose (``[Lm, N, K]``, what the benchmark's judge holds against the
reference's own scores) and the counters of ``STEP_COUNTERS``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.platform import default_interpret as _default_interpret
from ..ops.rope import apply_rope, attention_scale
from .configs import ModelConfig
from .llama import (MOE_LEAVES, DecodeGroup, Params, _act, _decode_targets,
                    _scaled, _wmat, decode_work, embed_lookup,
                    gather_last_hidden, lm_head_logits, mixed_hidden_out,
                    mixed_layout, moe_capacity, moe_experts, moe_item_rows,
                    moe_route,
                    moe_share_counts)

__all__ = ["init_params", "init_params_with", "forward_paged_decode",
           "forward_paged_mixed", "lm_head_logits", "gather_last_hidden",
           "STEP_COUNTERS"]

#: what ``aux`` counts over a forward's expert layers, in the order the
#: serving programs hand them to the host: assignments routed (tokens x K),
#: those that fell on experts held here, held experts with at least one, and
#: the expert layers whose held assignments fitted ``moe_capacity`` (so
#: ``moe_experts`` ran over the compacted list) beside the expert layers run,
#: then the rows one grouped matmul of the layer multiplied
#: (``llama.moe_item_rows``)
STEP_COUNTERS = ("assignments", "local", "touched", "compact", "forwards",
                "item_rows")

LatentPool = tuple[jnp.ndarray]     # (latent,): [L, N, page, rank + rope]
Aux = dict[str, jnp.ndarray]


def _one_device(mesh: Any, interpret: bool | None,
                architecture: str = "kimi_k2") -> bool:
    if mesh is not None:
        raise ValueError(f"{architecture} serves on one device: a latent "
                         "page has no head axis to shard and the expert "
                         "layer no ep axis")
    return _default_interpret() if interpret is None else interpret


# ---------------------------------------------------------------- parameters
def _attention_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    H, Hq = cfg.hidden_size, cfg.num_heads
    return {"wq_a": (H, cfg.q_lora_rank),
            "wq_b": (cfg.q_lora_rank, Hq * cfg.head_dim),
            "wkv_a": (H, cfg.latent_width),
            "wkv_b": (cfg.kv_lora_rank,
                      Hq * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (Hq * cfg.v_head_dim, H)}


def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """The parameter tree, its matrices made by ``matmul(key, shape)`` (the
    contraction on axis -2) and its embedding by ``embed(key, shape)``:
    ``init_params`` draws them in ``dtype``, ``runtime/quant.py`` straight
    into int8. Norms are ones; the router and its selection bias float32."""
    H, Vh = cfg.hidden_size, cfg.vocab_rows
    Ld, Lm = cfg.first_k_dense, cfg.num_moe_layers
    E, El, I = cfg.num_experts, cfg.experts_local, cfg.moe_intermediate_size
    Is = cfg.shared_experts * I
    keys = iter(jax.random.split(key, 32))

    def stack(n: int, extra: dict[str, tuple[int, ...]]) -> dict:
        tree = {"attn_norm": jnp.ones((n, H), dtype),
                "q_a_norm": jnp.ones((n, cfg.q_lora_rank), dtype),
                "kv_a_norm": jnp.ones((n, cfg.kv_lora_rank), dtype),
                "mlp_norm": jnp.ones((n, H), dtype)}
        for name, shape in {**_attention_shapes(cfg), **extra}.items():
            tree[name] = matmul(next(keys), (n, *shape))
        return tree

    dense = stack(Ld, {"gate": (H, cfg.intermediate_size),
                       "up": (H, cfg.intermediate_size),
                       "down": (cfg.intermediate_size, H)})
    layers = stack(Lm, {"shared_gate": (H, Is), "shared_up": (H, Is),
                        "shared_down": (Is, H),
                        "moe_gate": (El, H, I), "moe_up": (El, H, I),
                        "moe_down": (El, I, H)})
    layers["router"] = jax.random.normal(
        next(keys), (Lm, H, E), jnp.float32) * H ** -0.5
    layers["router_bias"] = 0.1 * jax.random.normal(
        next(keys), (Lm, E), jnp.float32)
    return {"embed": embed(next(keys), (Vh, H)),
            "final_norm": jnp.ones((H,), dtype),
            "lm_head": matmul(next(keys), (H, Vh)),
            "dense": dense, "layers": layers}


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Random-init parameters at model shape, every matrix at
    ``fan_in^-1/2``."""
    def matmul(k, shape):
        return jax.random.normal(k, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)

    return init_params_with(cfg, key, dtype, matmul, matmul)


# ------------------------------------------------------------------- a layer
def _proj(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` in f32 with the leaf's per-channel scale applied."""
    m, s = _wmat(w, x.dtype)
    return _scaled(jnp.einsum("...h,hd->...d", x, m,
                              preferred_element_type=jnp.float32), s)


def _swiglu(x: jnp.ndarray, gate, up, down, cfg: ModelConfig) -> jnp.ndarray:
    act = (_act(_proj(x, gate), cfg) * _proj(x, up)).astype(x.dtype)
    return _proj(act, down)


def _split_ukv(lp: dict, cfg: ModelConfig):
    """``wkv_b`` as the absorbed path uses it: its matrix ``[rank, Hq, nope +
    v]`` in the activations' dtype and its per-channel scale ``[Hq, nope +
    v]`` (None unquantized), the SAME stored numbers the expanded form
    multiplies by."""
    Hq = cfg.num_heads
    width = cfg.qk_nope_head_dim + cfg.v_head_dim
    m, s = _wmat(lp["wkv_b"], lp["attn_norm"].dtype)
    return (m.reshape(cfg.kv_lora_rank, Hq, width),
            None if s is None else s.reshape(Hq, width))


def compressed_query(lp: dict, x: jnp.ndarray, cfg: ModelConfig):
    """``c_q`` [1, N, q_lora_rank]: the normed down projection every query
    head (and an indexer's) is projected up from."""
    return rms_norm(_proj(x, lp["wq_a"]).astype(x.dtype), lp["q_a_norm"],
                    cfg.rms_norm_eps)


def latent_and_query(lp: dict, x: jnp.ndarray, cfg: ModelConfig, positions,
                     cos_t, sin_t, c_q: jnp.ndarray | None = None):
    """One layer's attention inputs from normed ``x`` [1, N, H]: the latent
    rows to cache (``c`` normed, ``k_r`` rotated) and the absorbed queries
    ``[N, Hq, .]``, both ``cfg.latent_lanes`` wide: ``rank + rope`` numbers
    and zeros up to whole lane tiles. ``c_q``: :func:`compressed_query` of
    ``x`` where the caller already has it."""
    N = x.shape[1]
    Hq, nope = cfg.num_heads, cfg.qk_nope_head_dim
    rank = cfg.kv_lora_rank
    if c_q is None:
        c_q = compressed_query(lp, x, cfg)
    q = _proj(c_q, lp["wq_b"]).reshape(1, N, Hq, cfg.head_dim)
    ckv = _proj(x, lp["wkv_a"]).astype(x.dtype)
    c = rms_norm(ckv[..., :rank], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_r = apply_rope(ckv[..., rank:][:, :, None, :], positions, cos_t, sin_t)
    q_rope = apply_rope(q[..., nope:].astype(x.dtype), positions, cos_t,
                        sin_t)
    w, s = _split_ukv(lp, cfg)
    q_nope = q[..., :nope] if s is None else q[..., :nope] * s[:, :nope]
    q_abs = jnp.einsum("bthn,rhn->bthr", q_nope.astype(x.dtype),
                       w[:, :, :nope], preferred_element_type=jnp.float32)
    pad = cfg.latent_lanes - cfg.latent_width    # whole lane tiles, in zeros
    latent = jnp.concatenate(
        [c, k_r[:, :, 0, :], jnp.zeros((1, N, pad), x.dtype)], axis=-1)[0]
    q = jnp.concatenate([q_abs.astype(x.dtype), q_rope,
                         jnp.zeros((1, N, Hq, pad), x.dtype)], -1)[0]
    return latent, q


def attention_out(lp: dict, h: jnp.ndarray, o_lat: jnp.ndarray,
                  cfg: ModelConfig) -> jnp.ndarray:
    """``o~`` [N, Hq, rank] through ``W_uv`` and ``W_o``, added to ``h``."""
    nope = cfg.qk_nope_head_dim
    w, s = _split_ukv(lp, cfg)
    o = jnp.einsum("nhr,rhv->nhv", o_lat, w[:, :, nope:],
                   preferred_element_type=jnp.float32)
    if s is not None:
        o = o * s[:, nope:]
    o = o.astype(h.dtype).reshape(1, o.shape[0], -1)
    return h + _proj(o, lp["wo"]).astype(h.dtype)


def _dense_residual(lp: dict, h: jnp.ndarray, cfg: ModelConfig):
    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    y = _swiglu(x, lp["gate"], lp["up"], lp["down"], cfg)
    return h + y.astype(h.dtype)


def _sigmoid_route(flat: jnp.ndarray, lp: dict, cfg: ModelConfig):
    """This architecture's router: sigmoid scores, a selection bias."""
    return moe_route(
        flat, lp["router"], cfg.experts_per_token, sigmoid=True,
        bias=lp["router_bias"], scale=cfg.routed_scaling_factor)


def _moe_residual(lp: dict, moe: dict, layer, h: jnp.ndarray,
                  cfg: ModelConfig, route: Callable = _sigmoid_route):
    """Post-attention norm + shared expert + the routed experts held here +
    residual over ``h`` [1, N, H]; also the experts chosen [N, K] and the
    layer's ``STEP_COUNTERS``. ``route(flat, lp, cfg) -> (experts, gates)``:
    another architecture's router over the same expert layer."""
    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    flat = x.reshape(-1, x.shape[-1])
    top_idx, gates = route(flat, lp, cfg)
    y = moe_experts(flat, top_idx, gates, moe, cfg, layer)
    y = y + _swiglu(flat, lp["shared_gate"], lp["shared_up"],
                    lp["shared_down"], cfg)
    routed, local, touched = moe_share_counts(top_idx, cfg)
    counts = jnp.stack([routed, local, touched,
                        (local <= moe_capacity(top_idx.size, cfg)
                         ).astype(jnp.int32),
                        jnp.asarray(1, jnp.int32),
                        moe_item_rows(top_idx, cfg)])
    return h + y.reshape(h.shape).astype(h.dtype), top_idx, counts


def _run_layers(params: Params, cfg: ModelConfig, h, pool, attend):
    """The leading dense layers, then the scan over the expert layers.
    ``attend(lp, layer, h, pool) -> (h after attention, pool)``. Returns
    (h, pool, aux)."""
    Ld = cfg.first_k_dense

    def dense_body(carry, xs):
        h, pool = carry
        lp, layer = xs
        h, pool = attend(lp, layer, h, pool)
        return (_dense_residual(lp, h, cfg), pool), None

    (h, pool), _ = jax.lax.scan(
        dense_body, (h, pool),
        (params["dense"], jnp.arange(Ld, dtype=jnp.int32)))

    layers = params["layers"]
    scanned = {k: v for k, v in layers.items() if k not in MOE_LEAVES}
    moe = {k: layers[k] for k in MOE_LEAVES}

    def moe_body(carry, xs):
        h, pool = carry
        lp, i = xs
        h, pool = attend(lp, Ld + i, h, pool)
        h, top_idx, counts = _moe_residual(lp, moe, i, h, cfg)
        return (h, pool), (top_idx, counts)

    (h, pool), (experts, counts) = jax.lax.scan(
        moe_body, (h, pool),
        (scanned, jnp.arange(cfg.num_moe_layers, dtype=jnp.int32)))
    counts = jnp.sum(counts, axis=0)
    return h, pool, {"experts": experts,
                     **{n: counts[i] for i, n in enumerate(STEP_COUNTERS)}}


# ------------------------------------------------------------------ forwards
def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1] one token a slot
    pools: LatentPool,
    page_table: jnp.ndarray,   # [B, Pmax]
    lengths: jnp.ndarray,      # [B] valid length BEFORE this token
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,   # [B]; False rows -> scratch
    mesh: Any = None,
) -> tuple[jnp.ndarray, LatentPool, Aux]:
    """One decode step over the latent pool. Returns (hidden [B, 1, H],
    pools, aux)."""
    from ..ops.mla_attention import mla_decode_attention

    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    B = input_ids.shape[0]
    (pool,) = pools
    page_size = pool.shape[2]
    positions = lengths[None, :]
    pid, off = _decode_targets(page_table, lengths, write_mask, page_size)
    work = decode_work(page_table, lengths + 1)
    scale = attention_scale(cfg)
    h = embed_lookup(params["embed"], input_ids.reshape(1, B),
                     params["final_norm"].dtype)

    def attend(lp, layer, h, pool):
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        latent, q = latent_and_query(lp, x, cfg, positions, cos_t, sin_t)
        pool = pool.at[layer, pid, off].set(latent.astype(pool.dtype))
        o = mla_decode_attention(q, pool, *work, layer, rank=cfg.kv_lora_rank,
                                 scale=scale, interpret=interpret,
                                 sliding_window=cfg.sliding_window)
        return attention_out(lp, h, o, cfg), pool

    h, pool, aux = _run_layers(params, cfg, h, pool, attend)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h.reshape(B, 1, -1), (pool,), aux


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc] per-lane query span, padded
    pools: LatentPool,
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
) -> tuple[jnp.ndarray, LatentPool, Aux]:
    """One mixed step over the tokens it has (lanes, ``rows``, ``decode`` and
    what comes back as ``llama.forward_paged_mixed``): the lanes' spans
    through the ragged latent kernel, the decode group through the decode
    kernel, one pass over the weights. Returns (hidden, pools, aux)."""
    from ..ops.mla_attention import mla_decode_attention, mla_ragged_attention

    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    R, Qc = input_ids.shape
    (pool,) = pools
    lay = mixed_layout(input_ids, page_table, hist, q_lens, write_mask, rows,
                       decode, pool)
    nd = lay.n_dec
    rank, scale = cfg.kv_lora_rank, attention_scale(cfg)
    h = embed_lookup(params["embed"], lay.ids, params["final_norm"].dtype)

    def attend(lp, layer, h, pool):
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        latent, q = latent_and_query(lp, x, cfg, lay.positions, cos_t, sin_t)
        # the step's latent rows go in BEFORE it attends: a chunk reads its
        # own earlier tokens back through the page chain
        pool = pool.at[layer, lay.pid, lay.off].set(latent.astype(pool.dtype))
        lane_q = q[nd:].reshape(R, Qc, *q.shape[1:]).transpose(0, 2, 1, 3)
        lane = mla_ragged_attention(
            lane_q, pool, lay.lane_table, hist, q_lens, layer, rank=rank,
            scale=scale, interpret=interpret,
            sliding_window=cfg.sliding_window)
        o = lane.transpose(0, 2, 1, 3).reshape(R * Qc, -1, rank)
        if nd:
            dec = mla_decode_attention(q[:nd], pool, *lay.work, layer,
                                       rank=rank, scale=scale,
                                       interpret=interpret,
                                       sliding_window=cfg.sliding_window)
            o = jnp.concatenate([dec, o], axis=0)
        return attention_out(lp, h, o, cfg), pool

    h, pool, aux = _run_layers(params, cfg, h, pool, attend)
    h = mixed_hidden_out(lay, h, q_lens, rows)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, (pool,), aux
