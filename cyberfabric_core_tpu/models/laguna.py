"""Laguna (``model_type: laguna``; Laguna-S-2.1): GQA attention on K/V pages
in TWO page groups, two kinds of attention layer that differ in their query
heads, their rotary tables and their window, a sigmoid gate a head, a leading
dense layer and then softmax-routed experts beside a shared expert. Written
from the published config and the conventions of the family its keys name
(``benchmark/configs/laguna-s-2.1-int8.json`` lists what is assumed);
RMSNorm, pre-norm residual, no bias anywhere, an untied head.

    h = Embed(ids);  layer l is FULL where ``cfg.layer_is_full(l)`` (l % 4 == 0)
    x = RMSNorm(h);  q = x W_q [H_l, D];  k, v = x W_k, x W_v [Hkv, D]
        H_l = cfg.num_heads (full) or cfg.window_heads (window)
    full:    q, k rotated on the head's leading ``partial_rotary_factor`` under
             YaRN tables (times 0.1 ln(rope_factor) + 1); every key s <= t
    window:  q, k rotated whole under plain ``window_rope_theta`` tables;
             keys t - sliding_window < s <= t
    a_j = softmax(q_j k^T / sqrt(D)) v,  query head j on kv head j // (H_l / Hkv)
    h <- h + [sigmoid(x W_g)_j a_j]_j W_o              (a gate a head a token)
    x' = RMSNorm(h)
    l < first_k_dense:  h <- h + SwiGLU(x')
    after them:  s = softmax(x' W_r) in float32; the K largest; g_e =
                 routed_scaling_factor s_e / sum_chosen s
                 h <- h + SwiGLU_shared(x') + sum_{e chosen, held} g_e SwiGLU_e(x')
    logits = RMSNorm(h) W_head

**The cache is two page groups of K/V pages** (``runtime/paged.py``): ``pools
= (k, v, window_k, window_v)``, the first pair ``[full layers, P, page, Hkv
D]`` and the second ``[window layers, window pages, page, Hkv D]``, and
``page_table [B, 2 Pmax]`` is the full group's table, then the window
group's, under the SAME logical page index (``motif._tables``). A layer
writes and reads the pair of its kind at its index AMONG THE LAYERS OF ITS
KIND (``cfg.full_layers_before``). One program holds two instances of each
K/V kernel (``ops/paged_attention.py``), each over its own group's table
(``llama.decode_work``; a window layer's walk starts at its span's first
page) and under its own name in a device trace (``_NAMES``); the scheduler
gives back the window pages left of a row's window and the table then names
scratch there, which no walk reaches.

``params``: ``full`` and ``window`` stack the attention matrices of their
kind (``wq`` and ``wo`` differ in shape), ``dense`` the leading layers'
norms and MLP, ``layers`` the expert layers' norms, float32 router, shared
expert and the held experts. A chip's share of the experts and of the
vocabulary as kimi_k2 has it (``llama.moe_experts``), and kimi_k2's
``STEP_COUNTERS``. **The stack is cut as motif's is** (``motif.run_plan`` over
``motif.layer_plan``): the layers ahead of the first full expert layer as
runs of one kind, then a scan whose body is one period (full, then a scan
over the window layers behind it), then the rest as runs; the served 12
layers are a dense full layer, a run of 3 window layers and 2 units, three
kinds of body.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..ops.attention import attention_with_cache
from ..ops.norms import rms_norm
from ..ops.platform import default_interpret as _default_interpret
from .configs import ModelConfig
from .granite_hybrid import _at
from .kimi_k2 import STEP_COUNTERS, _dense_residual, _moe_residual, _proj
from .llama import (MOE_LEAVES, DecodeGroup, Params, _attn_out,
                    _decode_targets, _qkv_proj, decode_work, embed_lookup,
                    gather_last_hidden, lm_head_logits, mixed_attention,
                    mixed_hidden_out, mixed_layout, moe_route)
from .motif import _kind, _tables, run_plan

__all__ = ["init_params", "init_params_with", "forward",
           "forward_paged_decode", "forward_paged_mixed", "lm_head_logits",
           "gather_last_hidden", "STEP_COUNTERS"]

Pools = tuple[jnp.ndarray, ...]     # (k, v, window_k, window_v)
Aux = dict[str, jnp.ndarray]

# the two call sites of each kernel, under the names a device trace shows
_NAMES = {True: "gqa_full", False: "gqa_window"}
_SCOPES = {True: "laguna_full_layer", False: "laguna_window_layer"}


def _heads(cfg: ModelConfig, full: bool) -> int:
    return cfg.num_heads if full else cfg.window_heads


def _window(cfg: ModelConfig, full: bool) -> int | None:
    return None if full else cfg.sliding_window


def _kind_tables(cfg: ModelConfig, rope_tables: tuple) -> dict[bool, tuple]:
    """``ops/rope.rope_tables`` by layer kind: two pairs where the window
    layers have tables of their own, the one pair for both otherwise."""
    if cfg.window_rope_theta:
        return dict(zip((True, False), rope_tables))
    return {True: rope_tables, False: rope_tables}


# ---------------------------------------------------------------- parameters
def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """The parameter tree, its matrices made by ``matmul(key, shape)`` (the
    contraction on axis -2) and its embedding by ``embed(key, shape)``
    (``kimi_k2.init_params_with``'s contract). Norms are ones, the router
    float32 at ``hidden^-1/2``; embedding and head are the held rows of the
    vocabulary, the expert matrices the held experts."""
    H, Vh, D = cfg.hidden_size, cfg.vocab_rows, cfg.head_dim
    Lm = cfg.moe_layers
    Ld = cfg.num_layers - Lm
    E, El, I, Is = cfg.num_experts, cfg.experts_local, cfg.expert_width, \
        cfg.shared_width
    Dkv = cfg.num_kv_heads * D
    keys = iter(jax.random.split(key, 32))

    def attention(n: int, heads: int) -> dict:
        tree = {"wq": matmul(next(keys), (n, H, heads * D)),
                "wk": matmul(next(keys), (n, H, Dkv)),
                "wv": matmul(next(keys), (n, H, Dkv)),
                "wo": matmul(next(keys), (n, heads * D, H))}
        if cfg.head_gate:           # the gate is there where the tree holds it
            tree["w_gate"] = matmul(next(keys), (n, H, heads))
        return tree

    def norms(n: int) -> dict:
        return {"attn_norm": jnp.ones((n, H), dtype),
                "mlp_norm": jnp.ones((n, H), dtype)}

    params = {
        "embed": embed(next(keys), (Vh, H)),
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": matmul(next(keys), (H, Vh)),
        "full": attention(cfg.attention_layers, cfg.num_heads),
        "window": attention(cfg.window_layers, cfg.window_heads),
        "dense": {**norms(Ld),
                  "gate": matmul(next(keys), (Ld, H, cfg.intermediate_size)),
                  "up": matmul(next(keys), (Ld, H, cfg.intermediate_size)),
                  "down": matmul(next(keys), (Ld, cfg.intermediate_size, H))}}
    if Lm:
        params["layers"] = {
            **norms(Lm),
            "router": jax.random.normal(next(keys), (Lm, H, E), jnp.float32)
            * H ** -0.5,
            "shared_gate": matmul(next(keys), (Lm, H, Is)),
            "shared_up": matmul(next(keys), (Lm, H, Is)),
            "shared_down": matmul(next(keys), (Lm, Is, H)),
            "moe_gate": matmul(next(keys), (Lm, El, H, I)),
            "moe_up": matmul(next(keys), (Lm, El, H, I)),
            "moe_down": matmul(next(keys), (Lm, El, I, H))}
    return params


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Random-init parameters at model shape, every matrix at
    ``fan_in^-1/2``."""
    def matmul(k, shape):
        return jax.random.normal(k, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)

    return init_params_with(cfg, key, dtype, matmul, matmul)


# ------------------------------------------------------------------ the stack
def _softmax_route(flat: jnp.ndarray, lp: dict, cfg: ModelConfig):
    """This architecture's router: softmax scores, the gates renormalised
    over the chosen and scaled."""
    return moe_route(flat, lp["router"], cfg.experts_per_token,
                     scale=cfg.routed_scaling_factor)


def _run_layers(params: Params, cfg: ModelConfig, h, pools, positions,
                rope_tables, attend):
    """The stack over ``h`` [., ., H]. ``attend(full: bool, i, q, k, v,
    pools) -> (attention output [., ., heads D], pools)`` with ``i`` the
    layer's index in its page group and q, k, v as ``llama._qkv_proj``
    hands them over. Returns (h, pools, aux)."""
    Ld = cfg.num_layers - cfg.moe_layers
    tables = _kind_tables(cfg, rope_tables)
    layers = params.get("layers", {})
    every = {k: v for k, v in layers.items() if k not in MOE_LEAVES}
    moe = {k: layers[k] for k in MOE_LEAVES if k in layers}

    def one(carry, at, like: int):
        h, pools = carry
        dense, full = _kind(cfg, like)
        lp = _at(params["dense"], at) if dense else _at(every, at - Ld)
        before = cfg.full_layers_before(at) if cfg.window_layers else at
        i = before if full else at - before
        ap = _at(params["full" if full else "window"], i)
        heads = _heads(cfg, full)
        with jax.named_scope(_SCOPES[full]):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv_proj(ap, x, cfg, positions, *tables[full],
                                heads=heads)
            o, pools = attend(full, i, q, k, v, pools)
            if cfg.head_gate:
                gate = jax.nn.sigmoid(_proj(x, ap["w_gate"]))[..., None]
                o = (o.reshape(*gate.shape[:-1], cfg.head_dim) * gate
                     ).astype(h.dtype).reshape(o.shape)
            h = _attn_out(ap, h, o)
            if dense:
                return (_dense_residual(lp, h, cfg), pools), None
            h, top_idx, counts = _moe_residual(lp, moe, at - Ld, h, cfg,
                                               route=_softmax_route)
        return (h, pools), (top_idx, counts)

    (h, pools), aux = run_plan(cfg, (h, pools), one)
    return h, pools, aux


def _one_device(mesh: Any, interpret: bool | None) -> bool:
    if mesh is not None:
        raise ValueError("laguna serves on one device: the window page group "
                         "has no sharding and the expert layer no ep axis")
    return _default_interpret() if interpret is None else interpret


# ------------------------------------------------------------------ forwards
def forward(params: Params, cfg: ModelConfig, input_ids: jnp.ndarray,
            rope_tables: tuple) -> tuple[jnp.ndarray, Aux]:
    """The whole sequences ``input_ids`` [B, T] from position 0, no cache
    and no kernels (the window a mask): (hidden [B, T, H] after the final
    norm, aux). What the tests hold the paged forwards and the reference
    against; the programs serve through the paged forwards alone."""
    B, T = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    full_len = jnp.full((B,), T, jnp.int32)

    def attend(full, i, q, k, v, pools):
        o = attention_with_cache(q, k, v, positions, full_len,
                                 sliding_window=_window(cfg, full))
        return o.reshape(B, T, -1), pools

    h = embed_lookup(params["embed"], input_ids, params["final_norm"].dtype)
    h, _, aux = _run_layers(params, cfg, h, (), positions, rope_tables,
                            attend)
    return rms_norm(h, params["final_norm"], cfg.rms_norm_eps), aux


def _kernels(cfg: ModelConfig, interpret: bool):
    """(decode, ragged) by layer kind: each K/V kernel at its two call
    sites, the window and the trace's name the kind's."""
    from ..ops.paged_attention import (paged_decode_attention,
                                       ragged_paged_attention)

    def decode(full: bool):
        def attend(qq, kk, vv, work, ly):
            return paged_decode_attention(
                qq, kk, vv, *work, ly, interpret=interpret,
                sliding_window=_window(cfg, full),
                name=_NAMES[full] + "_decode_attention")
        return attend

    def ragged(full: bool):
        def attend(qq, kk, vv, pt, hh, ql, ly):
            return ragged_paged_attention(
                qq, kk, vv, pt, hh, ql, ly, interpret=interpret,
                sliding_window=_window(cfg, full),
                name=_NAMES[full] + "_ragged_attention")
        return attend

    return ({f: decode(f) for f in (True, False)},
            {f: ragged(f) for f in (True, False)})


def _written(pools: Pools, full: bool, i, pid, off, k, v):
    """``pools`` with the step's ``k`` and ``v`` [N, Hkv, D] of layer ``i``
    of its kind in that kind's pair, at (page, offset) ``pid``, ``off`` [N]:
    (k pool, v pool, pools)."""
    at = 0 if full else 2
    n = pid.shape[0]
    kp = pools[at].at[i, pid, off].set(
        k.reshape(n, -1).astype(pools[at].dtype))
    vp = pools[at + 1].at[i, pid, off].set(
        v.reshape(n, -1).astype(pools[at + 1].dtype))
    return kp, vp, (*pools[:at], kp, vp, *pools[at + 2:])


def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1] one token a slot
    pools: Pools,
    page_table: jnp.ndarray,   # [B, 2 Pmax]: full group, window group
    lengths: jnp.ndarray,      # [B] valid length BEFORE this token
    rope_tables: tuple,        # (full layers' pair, window layers' pair)
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,   # [B]; False rows -> scratch
    mesh: Any = None,
) -> tuple[jnp.ndarray, Pools, Aux]:
    """One decode step over both page groups. Returns (hidden [B, 1, H],
    pools, aux)."""
    interpret = _one_device(mesh, interpret)
    B = input_ids.shape[0]
    page_size = pools[0].shape[2]
    positions = lengths[None, :]
    decode, _ = _kernels(cfg, interpret)
    # (write targets, what the kernel walks) of the full group and of the
    # window group
    group = {}
    for full, table in zip((True, False), _tables(page_table)):
        group[full] = (
            _decode_targets(table, lengths, write_mask, page_size),
            decode_work(table, lengths + 1))

    def attend(full, i, q, k, v, pools):
        (pid, off), work = group[full]
        kp, vp, pools = _written(pools, full, i, pid, off, k[0], v[0])
        o = decode[full](q[0], kp, vp, work, i)
        return o.reshape(1, B, -1), pools

    h = embed_lookup(params["embed"], input_ids.reshape(1, B),
                     params["final_norm"].dtype)
    h, pools, aux = _run_layers(params, cfg, h, tuple(pools), positions,
                                rope_tables, attend)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h.reshape(B, 1, -1), pools, aux


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc] per-lane query span, padded
    pools: Pools,
    page_table: jnp.ndarray,   # [B, 2 Pmax]
    hist: jnp.ndarray,         # [R] tokens BEFORE each lane's span
    q_lens: jnp.ndarray,       # [R] span length (0 = idle lane)
    rope_tables: tuple,
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,
    mesh: Any = None,
    *,
    rows: jnp.ndarray | None = None,
    decode: DecodeGroup | None = None,
) -> tuple[jnp.ndarray, Pools, Aux]:
    """One mixed step over the tokens it has (lanes, ``rows``, ``decode``
    and what comes back as ``llama.forward_paged_mixed``) over both page
    groups. Returns (hidden, pools, aux)."""
    interpret = _one_device(mesh, interpret)
    dec, ragged = _kernels(cfg, interpret)
    lays = {full: mixed_layout(input_ids, table, hist, q_lens,
                               write_mask, rows, decode, pool)
            for full, table, pool in zip((True, False), _tables(page_table),
                                         pools[::2])}
    lay = lays[True]            # ids, positions and the split are both's

    def attend(full, i, q, k, v, pools):
        mine = lays[full]
        # the step's k/v go in BEFORE it attends: a chunk reads its own
        # earlier tokens back through the page chain
        kp, vp, pools = _written(pools, full, i, mine.pid, mine.off, k[0],
                                 v[0])
        return mixed_attention(mine, q, kp, vp, hist, q_lens, i,
                               ragged[full], dec[full]), pools

    h = embed_lookup(params["embed"], lay.ids, params["final_norm"].dtype)
    h, pools, aux = _run_layers(params, cfg, h, tuple(pools), lay.positions,
                                rope_tables, attend)
    h = mixed_hidden_out(lay, h, q_lens, rows)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, pools, aux
