"""Motif (``model_type: Motif``; Motif-3-Beta): grouped differential attention
over a latent page, three layers in four behind a window, four residual
streams mixed by hyper-connections, PolyNorm MLPs, sigmoid-routed experts
beside a shared expert. Written from the published config and the papers its
keys name; RMSNorm, no bias anywhere but PolyNorm's.

A token's residual state is ``X [S, C]``, ``S = cfg.mhc_expansion_rate``
streams of the hidden size, carried through the stack LANE-DENSE as ``[N, S
C]``: a token's streams side by side (``_streams``). ``[N, S, C]`` would put
the four streams on the sublanes, where the chip stores tiles of four rows
and every pass moves vector registers a quarter (bfloat16) or a half
(float32) full: twice the time a sub-layer at 64 tokens, three times at 576
(PERF.md section 5 has the probe). **Around every sub-layer F** (attention,
then feed-forward), the maps float32, the streams in the activations' dtype
(mHC, arXiv:2512.24880; ``_mhc_maps``):

    x~ = RMSNorm(vec(X));  z = x~ phi
    H_pre = sigmoid(a0 z_pre + b_pre) [S];  H_post = 2 sigmoid(a1 z_post + b_post) [S]
    H_res = Sinkhorn(exp(a2 z_res + b_res)) [S, S]   (cfg.mhc_sinkhorn_iters
            alternations of row and column normalisation)
    u = H_pre X;  y = F(RMSNorm(u));  X <- H_res X + H_post^T y

``X_0`` is S copies of the embedding; the head reads ``RMSNorm(sum_i X[i])``.

**Attention (GDLA)** is kimi_k2's latent attention with three changes. The
80 query heads read 16 latent kv GROUPS: ``W_ukv`` has 16 heads' worth of
``[k_nope | v]``, group ``g`` serving signal heads ``4g..4g+3`` and noise
head ``g`` (the LAST ``cfg.num_noise_heads`` query heads). The output is
DIFFERENTIAL (``diff_v2``): ``o_h = A_h - lam_h A_noise(g(h))`` with ``lam_h =
sigmoid(x w_lam,h)`` a token a signal head, then gated elementwise by
``sigmoid(x W_gate)`` before ``W_o``. And a layer attends over everything
only where ``cfg.layer_is_full``; the others see the last
``cfg.sliding_window`` tokens.

It runs ABSORBED, as kimi_k2's does and for its reasons: ``q~_h = q_nope_h
W_uk,g(h)^T``, the kernels (``ops/mla_attention.py``, all 80 heads alike)
return ``o~_h = sum_s p c(s)``, the differential combine happens THERE, in
the latent (both operands share group ``g``'s ``W_uv``), and ``W_uv`` runs
for the 64 signal heads. Inside this file the heads are GROUP-MAJOR, five a
group with the noise head last (``_group_major``).

**The cache is two page groups** (``runtime/paged.py``): ``pools = (full,
window)``, each ``[layers of its kind, P, page, cfg.latent_lanes]``, and
``page_table [B, 2 Pmax]`` is the full group's table, then the window
group's, under the SAME logical page index. A window layer's kernels start
at its span's first page; the scheduler gives back the pages left of it and
the table then names scratch there.

    layers 0..first_k_dense-1:  F = W_down(PolyNorm(x W_gate) * (x W_up))
    after them:  s = sigmoid(x W_r) in float32; the K largest; g_e =
                 route_scale s_e / sum_chosen s;  F = shared(x) + sum g_e E_e(x)

each ``F``'s output clamped to ``+-cfg.hidden_clamp``; a chip's share of the
experts and of the vocabulary as kimi_k2 has it (``llama.moe_experts``).

The stack is ``params["dense"]`` and ``params["layers"]`` (the expert
layers); the main scan's body is ONE PERIOD of the window pattern, taken from
a full layer on (full, then a scan over the window layers behind it), the
layers ahead of the first full expert layer and those past the last whole
period run as scans over consecutive layers of one kind (``layer_plan``). Every entry point also returns ``aux``
(kimi_k2's: the experts chosen and ``STEP_COUNTERS``).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.rope import apply_rope, attention_scale
from .configs import ModelConfig
from .granite_hybrid import _at
from .kimi_k2 import STEP_COUNTERS, _one_device, _proj
from .llama import (MOE_LEAVES, DecodeGroup, Params, _act, _decode_targets,
                    _wmat, decode_work, embed_lookup, gather_last_hidden,
                    lm_head_logits, mixed_hidden_out, mixed_layout,
                    moe_capacity, moe_experts, moe_item_rows, moe_route,
                    moe_share_counts)

__all__ = ["init_params", "init_params_with", "forward_paged_decode",
           "forward_paged_mixed", "lm_head_logits", "gather_last_hidden",
           "STEP_COUNTERS", "sinkhorn", "layer_plan", "run_plan"]

Pools = tuple[jnp.ndarray, jnp.ndarray]     # (full, window) latent pools
Aux = dict[str, jnp.ndarray]


def _signal_heads(cfg: ModelConfig) -> int:
    return cfg.num_heads - cfg.num_noise_heads


def _mhc_width(cfg: ModelConfig) -> int:
    """Columns of a sub-layer's mHC map: pre, post, then the S x S mix."""
    S = cfg.mhc_expansion_rate
    return 2 * S + S * S


# ---------------------------------------------------------------- parameters
def _attention_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    H, G, Hs = cfg.hidden_size, cfg.num_kv_heads, _signal_heads(cfg)
    return {"wq_a": (H, cfg.q_lora_rank),
            "wq_b": (cfg.q_lora_rank, cfg.num_heads * cfg.head_dim),
            "wkv_a": (H, cfg.latent_width),
            "wkv_b": (cfg.kv_lora_rank,
                      G * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "w_lam": (H, Hs),
            "w_gate": (H, Hs * cfg.v_head_dim),
            "wo": (Hs * cfg.v_head_dim, H)}


def init_params_with(cfg: ModelConfig, key: jax.Array, dtype,
                     matmul: Callable, embed: Callable) -> Params:
    """The parameter tree, its matrices made by ``matmul(key, shape)`` (the
    contraction on axis -2) and its embedding by ``embed(key, shape)``
    (``kimi_k2.init_params_with``'s contract). Norms are ones; the router
    and the hyper-connections' maps float32, the maps drawn so that no
    stream is switched off and the mix is no permutation (``mhc_phi`` at
    ``(S C)^-1/2``, the three ``mhc_alpha`` at 1, ``mhc_bias`` at 0.5);
    PolyNorm's coefficients around a third each, its bias inside and outside
    the clamp."""
    H, Vh, S = cfg.hidden_size, cfg.vocab_rows, cfg.mhc_expansion_rate
    Ld, Lm = cfg.first_k_dense, cfg.num_moe_layers
    E, El, I = cfg.num_experts, cfg.experts_local, cfg.moe_intermediate_size
    Is = cfg.shared_experts * I
    keys = iter(jax.random.split(key, 48))

    def stack(n: int, units: int, extra: dict[str, tuple[int, ...]]) -> dict:
        """``units``: the layer's PolyNorm units (a dense MLP's one; the
        routed experts' and the shared expert's)."""
        tree = {"attn_norm": jnp.ones((n, H), dtype),
                "q_a_norm": jnp.ones((n, cfg.q_lora_rank), dtype),
                "kv_a_norm": jnp.ones((n, cfg.kv_lora_rank), dtype),
                "mlp_norm": jnp.ones((n, H), dtype),
                # a sub-layer's maps: [layer, attention | feed-forward, ...]
                "mhc_norm": jnp.ones((n, 2, S * H), jnp.float32),
                "mhc_phi": jax.random.normal(
                    next(keys), (n, 2, S * H, _mhc_width(cfg)), jnp.float32
                ) * (S * H) ** -0.5,
                "mhc_alpha": jnp.ones((n, 2, 3), jnp.float32),
                "mhc_bias": 0.5 * jax.random.normal(
                    next(keys), (n, 2, _mhc_width(cfg)), jnp.float32),
                "poly_coef": 1 / 3 + 0.1 * jax.random.normal(
                    next(keys), (n, units, 3), jnp.float32),
                "poly_bias": jax.random.normal(
                    next(keys), (n, units), jnp.float32)}
        for name, shape in {**_attention_shapes(cfg), **extra}.items():
            tree[name] = matmul(next(keys), (n, *shape))
        return tree

    dense = stack(Ld, 1, {"gate": (H, cfg.intermediate_size),
                          "up": (H, cfg.intermediate_size),
                          "down": (cfg.intermediate_size, H)})
    layers = stack(Lm, 2, {"shared_gate": (H, Is), "shared_up": (H, Is),
                           "shared_down": (Is, H),
                           "moe_gate": (El, H, I), "moe_up": (El, H, I),
                           "moe_down": (El, I, H)})
    layers["router"] = jax.random.normal(
        next(keys), (Lm, H, E), jnp.float32) * H ** -0.5
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


# ---------------------------------------------------------- hyper-connections
def sinkhorn(m: jnp.ndarray, iters: int) -> jnp.ndarray:
    """``iters`` alternations of row and column normalisation of the
    positive ``m`` [N, S, S], as the scalings they compose to: ``diag(r) m
    diag(c)`` with ``r <- 1 / (m c)``, ``c <- 1 / (m^T r)`` from ``c = 1``
    (two ``[N, S]`` vectors carried, not the matrix). 6.6-8.2 us for 20
    alternations on the chip at 64 and at 576 tokens (PERF.md section 5)."""
    c = jnp.ones_like(m[:, 0, :])
    for _ in range(iters):
        r = 1.0 / jnp.sum(m * c[:, None, :], axis=2)
        c = 1.0 / jnp.sum(m * r[:, :, None], axis=1)
    return r[:, :, None] * m * c[:, None, :]


def _streams(X: jnp.ndarray, S: int) -> list[jnp.ndarray]:
    """The ``S`` streams of ``X`` [N, S C], each [N, C] float32: runs of
    whole lane tiles, no reshape (a view ``[N, S, C]`` is the layout this
    file does not carry)."""
    return [x.astype(jnp.float32) for x in jnp.split(X, S, axis=-1)]


def _mhc_maps(lp: dict, sub: int, X: jnp.ndarray, cfg: ModelConfig):
    """The three maps of sub-layer ``sub`` (0 attention, 1 feed-forward)
    from the streams ``X`` [N, S C]: (H_pre [N, S], H_post [N, S], H_res
    [N, S, S]), float32."""
    N, S = X.shape[0], cfg.mhc_expansion_rate
    x = X.astype(jnp.float32)
    # the norm's weight goes onto phi (S C x M products for N x S C) and the
    # row's rsqrt onto the product (a scalar a token commutes with it): the
    # product reads the streams themselves, no normed float32 copy of them
    z = jnp.dot(x, lp["mhc_norm"][sub][:, None] * lp["mhc_phi"][sub],
                precision=jax.lax.Precision.HIGHEST)
    z = z * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    a, b = lp["mhc_alpha"][sub], lp["mhc_bias"][sub]
    pre = jax.nn.sigmoid(a[0] * z[:, :S] + b[:S])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, S:2 * S] + b[S:2 * S])
    res = sinkhorn(jnp.exp(a[2] * z[:, 2 * S:] + b[2 * S:]).reshape(N, S, S),
                   cfg.mhc_sinkhorn_iters)
    return pre, post, res


def hyper_connected(lp: dict, sub: int, X: jnp.ndarray, cfg: ModelConfig,
                    norm: jnp.ndarray, f: Callable):
    """One sub-layer ``f(x [1, N, C]) -> (y [N, C] f32, extra)`` around the
    streams ``X`` [N, S C]. Returns (X, extra). Both mixes are a token's
    scalar times a stream's slab, summed: no product over a 4 x 4 matrix a
    token for XLA to tile."""
    S = cfg.mhc_expansion_rate
    with jax.named_scope("mhc"):
        pre, post, res = _mhc_maps(lp, sub, X, cfg)
        xs = _streams(X, S)
        u = sum(pre[:, s:s + 1] * xs[s] for s in range(S))
    y, extra = f(rms_norm(u.astype(X.dtype)[None], norm, cfg.rms_norm_eps))
    if cfg.hidden_clamp:
        y = jnp.clip(y, -cfg.hidden_clamp, cfg.hidden_clamp)
    with jax.named_scope("mhc"):
        X = jnp.concatenate(
            [post[:, s:s + 1] * y
             + sum(res[:, s, t, None] * xs[t] for t in range(S))
             for s in range(S)], axis=-1).astype(X.dtype)
    return X, extra


# ------------------------------------------------------------------ attention
def _split_ukv(lp: dict, cfg: ModelConfig):
    """``wkv_b`` as the absorbed path uses it: ``[rank, G, nope + v]`` in the
    activations' dtype and its per-channel scale ``[G, nope + v]`` (None
    unquantized)."""
    G = cfg.num_kv_heads
    width = cfg.qk_nope_head_dim + cfg.v_head_dim
    m, s = _wmat(lp["wkv_b"], lp["attn_norm"].dtype)
    return (m.reshape(cfg.kv_lora_rank, G, width),
            None if s is None else s.reshape(G, width))


def _group_major(q: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """``q`` [..., Hq, D] in the published order (signal heads, then the
    noise heads) as ``[..., G, Hq/G, D]``: a group's signal heads, then its
    noise head."""
    G, Hs = cfg.num_kv_heads, _signal_heads(cfg)
    lead = q.shape[:-2]
    return jnp.concatenate(
        [q[..., :Hs, :].reshape(*lead, G, Hs // G, q.shape[-1]),
         q[..., Hs:, :].reshape(*lead, G, -1, q.shape[-1])], axis=-2)


def latent_and_query(lp: dict, x: jnp.ndarray, cfg: ModelConfig, positions,
                     cos_t, sin_t):
    """One layer's attention inputs from normed ``x`` [1, N, H]
    (``kimi_k2.latent_and_query`` over kv groups): the latent rows to cache
    and the absorbed queries ``[N, Hq, .]``, group-major, both
    ``cfg.latent_lanes`` wide."""
    N = x.shape[1]
    Hq, nope, rank = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c_q = rms_norm(_proj(x, lp["wq_a"]).astype(x.dtype), lp["q_a_norm"],
                   cfg.rms_norm_eps)
    q = _proj(c_q, lp["wq_b"]).reshape(1, N, Hq, cfg.head_dim)
    ckv = _proj(x, lp["wkv_a"]).astype(x.dtype)
    c = rms_norm(ckv[..., :rank], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_r = apply_rope(ckv[..., rank:][:, :, None, :], positions, cos_t, sin_t)
    q_rope = apply_rope(q[..., nope:].astype(x.dtype), positions, cos_t,
                        sin_t)
    w, s = _split_ukv(lp, cfg)
    q_nope = _group_major(q[..., :nope], cfg)           # [1, N, G, 5, nope]
    if s is not None:
        q_nope = q_nope * s[:, None, :nope]
    q_abs = jnp.einsum("btgqn,rgn->btgqr", q_nope.astype(x.dtype),
                       w[:, :, :nope], preferred_element_type=jnp.float32)
    pad = cfg.latent_lanes - cfg.latent_width    # whole lane tiles, in zeros
    latent = jnp.concatenate(
        [c, k_r[:, :, 0, :], jnp.zeros((1, N, pad), x.dtype)], axis=-1)[0]
    q = jnp.concatenate(
        [q_abs.astype(x.dtype).reshape(1, N, Hq, rank),
         _group_major(q_rope, cfg).reshape(1, N, Hq, -1),
         jnp.zeros((1, N, Hq, pad), x.dtype)], -1)[0]
    return latent, q


def attention_out(lp: dict, x: jnp.ndarray, o_lat: jnp.ndarray,
                  cfg: ModelConfig) -> jnp.ndarray:
    """``o~`` [N, Hq, rank], group-major, to the sub-layer's output [N, C]
    f32: the differential combine in the latent, ``W_uv`` for the signal
    heads, the output gate, ``W_o``. ``x`` [1, N, H] is the sub-layer's
    normed input (``lam`` and the gate read it)."""
    N = o_lat.shape[0]
    G, nope = cfg.num_kv_heads, cfg.qk_nope_head_dim
    o_lat = o_lat.reshape(N, G, -1, o_lat.shape[-1]).astype(jnp.float32)
    lam = jax.nn.sigmoid(_proj(x, lp["w_lam"]))[0].reshape(N, G, -1)
    diff = o_lat[:, :, :-1] - lam[..., None] * o_lat[:, :, -1:]
    w, s = _split_ukv(lp, cfg)
    o = jnp.einsum("ngqr,rgv->ngqv", diff.astype(x.dtype), w[:, :, nope:],
                   preferred_element_type=jnp.float32)
    if s is not None:
        o = o * s[:, None, nope:]
    o = o.reshape(1, N, -1) * jax.nn.sigmoid(_proj(x, lp["w_gate"]))
    return _proj(o.astype(x.dtype), lp["wo"])[0]


# --------------------------------------------------------------- feed-forward
def _poly_mlp(x: jnp.ndarray, gate, up, down, poly,
              cfg: ModelConfig) -> jnp.ndarray:
    act = (_act(_proj(x, gate), cfg, poly) * _proj(x, up)).astype(x.dtype)
    return _proj(act, down)


def _dense_ffn(lp: dict, x: jnp.ndarray, cfg: ModelConfig):
    poly = (lp["poly_coef"][0], lp["poly_bias"][0])
    return _poly_mlp(x, lp["gate"], lp["up"], lp["down"], poly, cfg)[0], None


def _moe_ffn(lp: dict, moe: dict, layer, x: jnp.ndarray, cfg: ModelConfig):
    """Shared expert + the routed experts held here over ``x`` [1, N, H]:
    (y [N, H] f32, (the experts chosen [N, K], the layer's
    ``STEP_COUNTERS``))."""
    flat = x.reshape(-1, x.shape[-1])
    top_idx, gates = moe_route(
        flat, lp["router"], cfg.experts_per_token, sigmoid=True,
        scale=cfg.routed_scaling_factor)
    y = moe_experts(flat, top_idx, gates, moe, cfg, layer,
                    poly=(lp["poly_coef"][0], lp["poly_bias"][0]))
    y = y + _poly_mlp(flat, lp["shared_gate"], lp["shared_up"],
                      lp["shared_down"],
                      (lp["poly_coef"][1], lp["poly_bias"][1]), cfg)
    routed, local, touched = moe_share_counts(top_idx, cfg)
    counts = jnp.stack([routed, local, touched,
                        (local <= moe_capacity(top_idx.size, cfg)
                         ).astype(jnp.int32),
                        jnp.asarray(1, jnp.int32),
                        moe_item_rows(top_idx, cfg)])
    return y, (top_idx, counts)


# ------------------------------------------------------------------ the stack
def _kind(cfg: ModelConfig, layer: int) -> tuple[bool, bool]:
    """(dense, full) of layer ``layer``: what its body is compiled from."""
    return (layer < cfg.first_k_dense or not cfg.num_experts,
            cfg.layer_is_full(layer))


def _runs(cfg: ModelConfig, first: int, end: int) -> list[tuple[int, int]]:
    """Layers ``first .. end - 1`` as runs (first layer, count) of one kind."""
    runs: list[tuple[int, int]] = []
    for layer in range(first, end):
        if runs and _kind(cfg, layer) == _kind(cfg, runs[-1][0]):
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append((layer, 1))
    return runs


def layer_plan(cfg: ModelConfig) -> tuple[list, tuple[int, int], list]:
    """The stack as (runs ahead of the units, (the first unit's layer, the
    units), runs after them). A UNIT is a full expert layer and the window
    layers of the period it closes INTO the next (full, window, ..., window:
    ``cfg.sliding_window_period`` layers), so every unit is the same two
    bodies; a run is consecutive layers of one kind, one body. 27 layers of
    the served share: the 2 dense layers, layer 2, then 6 units from layer 3
    on, FOUR bodies to compile where a layer a body would be 27."""
    P, L = cfg.sliding_window_period, cfg.num_layers
    if not P:                       # one kind of attention layer: no units
        return _runs(cfg, 0, L), (L, 0), []
    first = next((i for i in range(cfg.first_k_dense, L)
                  if cfg.layer_is_full(i)), L)
    units = (L - first) // P
    return (_runs(cfg, 0, first), (first, units),
            _runs(cfg, first + units * P, L))


def run_plan(cfg: ModelConfig, carry, one: Callable):
    """The stack cut as :func:`layer_plan` says, for any model whose layers
    are (dense or expert) x (full or window): ``one(carry, at, like) ->
    (carry, chosen)`` runs layer ``at`` (traced inside a scan), whose kind is
    layer ``like``'s, ``chosen`` (the experts chosen [N, K], the layer's
    ``STEP_COUNTERS``) or None of a dense layer. Returns (carry, aux)."""
    P = cfg.sliding_window_period

    def run(carry, first, count: int, like: int):
        """``count`` consecutive layers of layer ``like``'s kind from
        ``first`` on: (carry, (experts [count, N, K], counters [count, .])
        or None)."""
        def body(carry, at):
            return one(carry, at, like)

        return jax.lax.scan(
            body, carry, first + jnp.arange(count, dtype=jnp.int32))

    experts, counts = [], jnp.zeros((len(STEP_COUNTERS),), jnp.int32)

    def took(chosen):
        nonlocal counts
        if chosen is not None:
            experts.append(chosen[0].reshape(-1, *chosen[0].shape[-2:]))
            counts = counts + jnp.sum(chosen[1].reshape(-1, counts.shape[0]),
                                      axis=0)

    head, (first, units), tail = layer_plan(cfg)
    for at, count in head:
        carry, chosen = run(carry, at, count, at)
        took(chosen)
    if units:
        def unit(carry, at):
            carry, full = run(carry, at, 1, first)
            carry, window = run(carry, at + 1, P - 1, first + 1)
            return carry, jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), full, window)

        carry, chosen = jax.lax.scan(
            unit, carry, first + P * jnp.arange(units, dtype=jnp.int32))
        took(chosen)                                        # layer order
    for at, count in tail:
        carry, chosen = run(carry, at, count, at)
        took(chosen)
    aux = {**({"experts": jnp.concatenate(experts)} if experts else {}),
           **{name: counts[i] for i, name in enumerate(STEP_COUNTERS)}}
    return carry, aux


def _run_layers(params: Params, cfg: ModelConfig, X, pools, attend):
    """The stack over the streams ``X`` [N, S C]. ``attend(full: bool, lp,
    i, x, pools) -> (o~ [N, Hq, rank], pools)`` with ``i`` the layer's index
    in its page group. Returns (X, pools, aux)."""
    P, Ld = cfg.sliding_window_period, cfg.first_k_dense
    layers = params["layers"]
    every = {k: v for k, v in layers.items() if k not in MOE_LEAVES}
    moe = {k: layers[k] for k in MOE_LEAVES}

    def one(carry, at, like: int):
        X, pools = carry
        dense, full = _kind(cfg, like)
        lp = _at(params["dense"], at) if dense else _at(every, at - Ld)
        # the layer's index in its page group: the full layers before it,
        # or the window layers before it
        i = (at + 1) // P - 1 if full else at - (at + 1) // P

        def attention(x):
            o_lat, new = attend(full, lp, i, x, pools)
            return attention_out(lp, x, o_lat, cfg), new

        X, pools = hyper_connected(lp, 0, X, cfg, lp["attn_norm"], attention)
        ffn = (lambda x: _dense_ffn(lp, x, cfg)) if dense else (
            lambda x: _moe_ffn(lp, moe, at - Ld, x, cfg))
        X, chosen = hyper_connected(lp, 1, X, cfg, lp["mlp_norm"], ffn)
        return (X, pools), chosen

    (X, pools), aux = run_plan(cfg, (X, pools), one)
    return X, pools, aux


def _streams_in(params: Params, cfg: ModelConfig, ids: jnp.ndarray):
    """``ids`` [1, N] as the streams [N, S C]: S copies of the embedding,
    side by side."""
    h = embed_lookup(params["embed"], ids, params["final_norm"].dtype)[0]
    return jnp.concatenate([h] * cfg.mhc_expansion_rate, axis=-1)


def _streams_out(X: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The streams' sum [1, N, C], what the final norm reads."""
    return sum(_streams(X, cfg.mhc_expansion_rate)).astype(X.dtype)[None]


def _tables(page_table: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A row's page table as (the full group's, the window group's)."""
    pmax = page_table.shape[1] // 2
    return page_table[:, :pmax], page_table[:, pmax:]


# the two call sites of each kernel, under the names a device trace shows
_NAMES = {True: "gdla_full", False: "gdla_window"}


# ------------------------------------------------------------------ forwards
def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1] one token a slot
    pools: Pools,
    page_table: jnp.ndarray,   # [B, 2 Pmax]: full group, window group
    lengths: jnp.ndarray,      # [B] valid length BEFORE this token
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,   # [B]; False rows -> scratch
    mesh: Any = None,
) -> tuple[jnp.ndarray, Pools, Aux]:
    """One decode step over both page groups. Returns (hidden [B, 1, H],
    pools, aux)."""
    from ..ops.mla_attention import mla_decode_attention

    interpret = _one_device(mesh, interpret, "motif")
    cos_t, sin_t = rope_tables
    B = input_ids.shape[0]
    page_size = pools[0].shape[2]
    positions = lengths[None, :]
    scale = attention_scale(cfg)
    # (write targets, what the kernel walks) of the full group and of the
    # window group
    group = {}
    for full, table in zip((True, False), _tables(page_table)):
        group[full] = (
            _decode_targets(table, lengths, write_mask, page_size),
            decode_work(table, lengths + 1))

    def attend(full, lp, i, x, pools):
        (pid, off), work = group[full]
        latent, q = latent_and_query(lp, x, cfg, positions, cos_t, sin_t)
        pool = pools[not full]
        pool = pool.at[i, pid, off].set(latent.astype(pool.dtype))
        o = mla_decode_attention(
            q, pool, *work, i, rank=cfg.kv_lora_rank, scale=scale,
            interpret=interpret,
            sliding_window=None if full else cfg.sliding_window,
            name=_NAMES[full] + "_decode_attention")
        return o, (pool, pools[1]) if full else (pools[0], pool)

    X = _streams_in(params, cfg, input_ids.reshape(1, B))
    X, pools, aux = _run_layers(params, cfg, X, tuple(pools), attend)
    h = rms_norm(_streams_out(X, cfg), params["final_norm"], cfg.rms_norm_eps)
    return h.reshape(B, 1, -1), pools, aux


def forward_paged_mixed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [R, Qc] per-lane query span, padded
    pools: Pools,
    page_table: jnp.ndarray,   # [B, 2 Pmax]
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
    over both page groups). Returns (hidden, pools, aux)."""
    from ..ops.mla_attention import mla_decode_attention, mla_ragged_attention

    interpret = _one_device(mesh, interpret, "motif")
    cos_t, sin_t = rope_tables
    R, Qc = input_ids.shape
    lays = {full: mixed_layout(input_ids, table, hist, q_lens,
                               write_mask, rows, decode, pool)
            for full, table, pool in zip((True, False), _tables(page_table),
                                         pools)}
    lay = lays[True]            # ids, positions and the split are both's
    nd = lay.n_dec
    rank, scale = cfg.kv_lora_rank, attention_scale(cfg)

    def attend(full, lp, i, x, pools):
        mine = lays[full]
        window = None if full else cfg.sliding_window
        latent, q = latent_and_query(lp, x, cfg, lay.positions, cos_t, sin_t)
        pool = pools[not full]
        # the step's latent rows go in BEFORE it attends: a chunk reads its
        # own earlier tokens back through the page chain
        pool = pool.at[i, mine.pid, mine.off].set(latent.astype(pool.dtype))
        lane_q = q[nd:].reshape(R, Qc, *q.shape[1:]).transpose(0, 2, 1, 3)
        lane = mla_ragged_attention(
            lane_q, pool, mine.lane_table, hist, q_lens, i, rank=rank,
            scale=scale, interpret=interpret, sliding_window=window,
            name=_NAMES[full] + "_ragged_attention")
        o = lane.transpose(0, 2, 1, 3).reshape(R * Qc, -1, rank)
        if nd:
            dec = mla_decode_attention(
                q[:nd], pool, *mine.work, i, rank=rank, scale=scale,
                interpret=interpret, sliding_window=window,
                name=_NAMES[full] + "_decode_attention")
            o = jnp.concatenate([dec, o], axis=0)
        return o, (pool, pools[1]) if full else (pools[0], pool)

    X = _streams_in(params, cfg, lay.ids)
    X, pools, aux = _run_layers(params, cfg, X, tuple(pools), attend)
    h = mixed_hidden_out(lay, _streams_out(X, cfg), q_lens, rows)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, pools, aux
