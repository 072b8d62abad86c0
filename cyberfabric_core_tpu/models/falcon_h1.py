"""Falcon-H1: a decoder whose every block runs a Mamba-2 mixer beside GQA
attention on one normalised input, then a SwiGLU MLP (``model_type:
falcon_h1``; written from the published config and the Hugging Face
modelling code it names).

    x   = RMSNorm(h)
    o_a = attention_out_multiplier · W_o · GQA(RoPE(W_q x_a), key_multiplier ·
          RoPE(W_k x_a), W_v x_a),      x_a = x · attention_in_multiplier
    [z | xBC | dt] = (W_in (x · ssm_in_multiplier)) ⊙ ssm_multipliers
    xBC = SiLU(conv1d(xBC) + b);  Δ = softplus(dt + dt_bias);  A = −exp(A_log)
    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ;   y_t = S_t C_t + D x_t
    o_s = ssm_out_multiplier · W_out · RMSNorm_groups(y ⊙ SiLU(z))
    h  ← h + o_a + o_s
    h  ← h + mlp_multipliers[1] · W_down(SiLU(mlp_multipliers[0] · W_gate x′)
             ⊙ W_up x′),   x′ = RMSNorm(h)

``embedding_multiplier`` after the lookup, ``lm_head_multiplier`` on the
logits, untied head. The attention half and the MLP are ``models/llama.py``'s
``_qkv_proj`` / ``_attn_out`` / ``_mlp_residual`` and the paged kernels; the
mixer is ``ops/ssd.py``. No multiplier is folded into a matrix's scales: each
is one scalar multiply in the f32 epilogue of its matmul (XLA fuses it), so
nothing rests on a product of two f32 numbers being exact.

The entry points are the ones ``runtime/scheduler.py`` drives for the llama
family, with one more operand: the recurrent state beside the K/V pools,
``{"ssm": [L, rows, H, P, N] f32, "conv": [L, rows, K-1, C] f32}``; row ``b``
of a batch is row ``b`` of the slab (further rows are the pool's snapshots,
which these programs never touch). ``write_mask`` governs state as it governs
pages: a row marked False, or a mixed row with ``q_len`` 0, keeps its state
bit for bit. A mixed row whose history is 0 starts from the zero state, so
admission clears nothing.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.norms import rms_norm
from ..ops.platform import default_interpret as _default_interpret
from ..ops.ssd import (causal_conv, causal_conv_step, ssd_chunked,
                       ssm_state_update)
from . import llama
from .configs import ModelConfig
from .llama import (DecodeGroup, PagedPools, Params, _attn_out,
                    _decode_attend, _decode_targets, _embed_scale,
                    _mlp_residual, _qkv_proj, _ragged_attend, _scaled, _wmat,
                    decode_work, embed_lookup, gather_last_hidden,
                    lm_head_logits, mixed_attention, mixed_hidden_out,
                    mixed_layout)

__all__ = ["init_params", "init_mixer_small", "init_state",
           "forward_paged_decode", "forward_paged_mixed", "lm_head_logits",
           "gather_last_hidden"]

State = dict[str, jnp.ndarray]


def init_mixer_small(cfg: ModelConfig, key: jax.Array) -> dict[str, jnp.ndarray]:
    """The mixer's small f32 leaves, drawn so that a synthetic model's decays
    are neither 0 nor 1: ``A_log = log U(1, 16)`` and ``dt_bias`` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1] (the Mamba-2 defaults), so
    ``exp(Δ A)`` lies between about 0.2 and 0.999; ``D = 1``; conv taps
    U(±K^-1/2), a small conv bias; the gated norm's weight 1."""
    L, Hs, K, C = cfg.state_layers, cfg.ssm_heads, cfg.ssm_conv, cfg.ssm_conv_dim
    k = jax.random.split(key, 4)
    step = jnp.exp(jax.random.uniform(k[0], (L, Hs), jnp.float32,
                                      np.log(1e-3), np.log(1e-1)))
    return {
        "A_log": jnp.log(jax.random.uniform(k[1], (L, Hs), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "D": jnp.ones((L, Hs), jnp.float32),
        "conv_w": jax.random.uniform(k[2], (L, K, C), jnp.float32,
                                     -K ** -0.5, K ** -0.5),
        "conv_b": 0.1 * jax.random.normal(k[3], (L, C), jnp.float32),
        "ssm_norm": jnp.ones((L, cfg.ssm_inner), jnp.float32),
    }


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init parameters: the llama tree plus the mixer's leaves."""
    k_llama, k_in, k_out, k_small = jax.random.split(key, 4)
    params = llama.init_params(cfg, k_llama, dtype)
    H, L = cfg.hidden_size, cfg.num_layers

    def w(rng, *shape):
        return jax.random.normal(rng, shape, dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype)

    params["layers"].update({
        "ssm_in": w(k_in, L, H, cfg.ssm_proj_dim),
        "ssm_out": w(k_out, L, cfg.ssm_inner, H),
        **init_mixer_small(cfg, k_small)})
    return params


def init_state(cfg: ModelConfig, rows: int) -> State:
    """The zero state slab for ``rows`` rows (slots first, then snapshots),
    one layer for every layer that holds state."""
    L = cfg.state_layers
    return {"ssm": jnp.zeros((L, rows, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((L, rows, cfg.ssm_conv - 1, cfg.ssm_conv_dim),
                              jnp.float32)}


def _mup_vector(cfg: ModelConfig) -> np.ndarray:
    """``ssm_multipliers`` spread over the parts of the input projection."""
    gn = cfg.ssm_groups * cfg.ssm_state
    widths = (cfg.ssm_inner, cfg.ssm_inner, gn, gn, cfg.ssm_heads)
    return np.concatenate([np.full(n, m, np.float32)
                           for n, m in zip(widths, cfg.ssm_multipliers)])


def _mixer_in(lp: dict, x: jnp.ndarray, cfg: ModelConfig):
    """The input projection and its split: z [B, T, d_ssm], xBC [B, T, C]
    (conv input), dt [B, T, Hs], all f32."""
    w_m, w_s = _wmat(lp["ssm_in"], x.dtype)
    if cfg.ssm_in_multiplier != 1.0:
        x = x * jnp.asarray(cfg.ssm_in_multiplier, x.dtype)
    proj = _scaled(jnp.einsum("bth,hd->btd", x, w_m,
                              preferred_element_type=jnp.float32), w_s)
    if any(m != 1.0 for m in cfg.ssm_multipliers):
        proj = proj * _mup_vector(cfg)
    d, c = cfg.ssm_inner, cfg.ssm_conv_dim
    return proj[..., :d], proj[..., d: d + c], proj[..., d + c:]


def _split_xbc(xbc: jnp.ndarray, cfg: ModelConfig):
    """Conv output (after SiLU) → x [.., Hs, P], B and C [.., G, N]."""
    d, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :d].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim),
            xbc[..., d: d + gn].reshape(*lead, cfg.ssm_groups, cfg.ssm_state),
            xbc[..., d + gn:].reshape(*lead, cfg.ssm_groups, cfg.ssm_state))


def _mixer_out(lp: dict, y: jnp.ndarray, z: jnp.ndarray, cfg: ModelConfig,
               dtype) -> jnp.ndarray:
    """Gate first, then the grouped RMSNorm (``mamba_norm_before_gate``
    false), the output projection and its multiplier. ``y``, ``z``
    [B, T, d_ssm] f32."""
    g = y * jax.nn.silu(z)
    lead = g.shape[:-1]
    grouped = g.reshape(*lead, cfg.ssm_groups, -1)
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + cfg.rms_norm_eps)).reshape(
        *lead, cfg.ssm_inner) * lp["ssm_norm"]
    w_m, w_s = _wmat(lp["ssm_out"], dtype)
    out = _scaled(jnp.einsum("btd,dh->bth", normed.astype(dtype), w_m,
                             preferred_element_type=jnp.float32), w_s)
    if cfg.ssm_out_multiplier != 1.0:
        out = out * cfg.ssm_out_multiplier
    return out.astype(dtype)


def _layer_rows(slab: jnp.ndarray, layer, rows: int) -> jnp.ndarray:
    """Rows ``[:rows]`` of one layer of a stacked slab: a slice the size of
    what the caller computes on, never the slab."""
    return jax.lax.dynamic_slice(
        slab, (layer,) + (0,) * (slab.ndim - 1),
        (1, rows) + slab.shape[2:])[0]


def _store_rows(slab: jnp.ndarray, layer, rows: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(
        slab, rows[None], (layer,) + (0,) * (slab.ndim - 1))


def _lane_rows(slab: jnp.ndarray, layer, rows, n: int) -> jnp.ndarray:
    """The ``n`` lanes' rows of one layer of a slab, ``[n, ...]``: rows
    ``[:n]`` where ``rows`` is None (lane r = row r), else the named rows,
    each a row-sized slice. Either way what is read is the lanes' size,
    never a layer of the slab."""
    if rows is None:
        return _layer_rows(slab, layer, n)
    tail = (0,) * (slab.ndim - 2)
    return jnp.concatenate([
        jax.lax.dynamic_slice(slab, (layer, rows[r]) + tail,
                              (1, 1) + slab.shape[2:])[0] for r in range(n)])


def _store_lane_rows(slab: jnp.ndarray, layer, rows, new: jnp.ndarray
                     ) -> jnp.ndarray:
    if rows is None:
        return _store_rows(slab, layer, new)
    tail = (0,) * (slab.ndim - 2)
    for r in range(new.shape[0]):
        slab = jax.lax.dynamic_update_slice(
            slab, new[r][None, None], (layer, rows[r]) + tail)
    return slab


def _one_device(mesh: Any, interpret: bool | None) -> bool:
    """The forwards' shared entry checks; returns ``interpret`` resolved."""
    if mesh is not None:
        raise ValueError("falcon_h1 serves on one device: the state slab has "
                         "no tp sharding")
    return _default_interpret() if interpret is None else interpret


def _attn_in(x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.attention_in_multiplier != 1.0:
        return x * jnp.asarray(cfg.attention_in_multiplier, x.dtype)
    return x


def _mixer_step(lp: dict, layer, cfg: ModelConfig, u, dt, ssm, conv,
                run, act, kernel: bool):
    """One token of rows ``[:B]`` of the slab (a decode step; a mixed step's
    decode group): ``u`` [B, C] and ``dt`` [B, Hs] f32 from the input
    projection. The conv tail moves with a row-sized slice, the state in
    place under the ``ssm_state_update`` kernel; rows with ``run`` False keep
    both bit for bit. Returns (y [B, d_ssm] f32, ssm, conv)."""
    B = u.shape[0]
    tail = _layer_rows(conv, layer, B)
    xbc, new_tail = causal_conv_step(u, tail, lp["conv_w"], lp["conv_b"])
    conv = _store_rows(conv, layer, jnp.where(
        run[:, None, None], new_tail, tail))
    xs_, b_mat, c_mat = _split_xbc(jax.nn.silu(xbc).astype(act), cfg)
    y, ssm = ssm_state_update(
        ssm, layer, xs_, jax.nn.softplus(dt + lp["dt_bias"]),
        -jnp.exp(lp["A_log"]), b_mat, c_mat, run, kernel=kernel)
    y = y + lp["D"][:, None] * xs_.astype(jnp.float32)
    return y.reshape(B, -1), ssm, conv


def _mixer_chunk(lp: dict, layer, cfg: ModelConfig, u, dt, ssm, conv, rows,
                 fresh, advance, span, act):
    """The lanes' chunk of a mixed step through the chunked form: ``u``
    [R, Qc, C] and ``dt`` [R, Qc, Hs] f32 from the input projection, each
    lane on its own row of the slab (``rows``; None: lane r = row r), from
    the zero state where ``fresh`` [R]. Only the lanes' rows are read and
    written; a lane with ``advance`` False keeps state and conv tail bit for
    bit. Returns (y [R, Qc, d_ssm] f32, ssm, conv)."""
    R, Qc = u.shape[:2]
    tail = _lane_rows(conv, layer, rows, R)
    s_old = _lane_rows(ssm, layer, rows, R)
    xbc, new_tail = causal_conv(
        u, jnp.where(fresh[:, None, None], 0.0, tail), lp["conv_w"],
        lp["conv_b"], span)
    xs_, b_mat, c_mat = _split_xbc(jax.nn.silu(xbc).astype(act), cfg)
    y, s_new = ssd_chunked(
        xs_, jax.nn.softplus(dt + lp["dt_bias"]), -jnp.exp(lp["A_log"]),
        b_mat, c_mat, lp["D"],
        jnp.where(fresh[:, None, None, None], 0.0, s_old), span,
        cfg.ssm_chunk)
    conv = _store_lane_rows(conv, layer, rows, jnp.where(
        advance[:, None, None], new_tail, tail))
    ssm = _store_lane_rows(ssm, layer, rows, jnp.where(
        advance[:, None, None, None], s_new, s_old))
    return y.reshape(R, Qc, -1), ssm, conv


def forward_paged_decode(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,    # [B, 1]
    pools: PagedPools,
    page_table: jnp.ndarray,   # [B, Pmax]
    lengths: jnp.ndarray,      # [B] valid length BEFORE this token
    rope_tables: tuple[jnp.ndarray, jnp.ndarray],
    interpret: bool | None = None,
    write_mask: jnp.ndarray | None = None,
    mesh: Any = None,
    *,
    state: State,
) -> tuple[jnp.ndarray, PagedPools, State]:
    """One decode step over the page pool and the state slab. Returns
    (hidden [B, 1, H], pools, state). K/V as ``llama.forward_paged_decode``;
    the recurrent state of row ``b`` is read and written in place by the
    ``ssm_state_update`` kernel, and stays as it was where ``write_mask`` is
    False."""
    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
    B = input_ids.shape[0]
    Hq, D = cfg.num_heads, cfg.head_dim
    positions = lengths[:, None]
    if write_mask is None:
        write_mask = jnp.ones((B,), bool)
    pid, off = _decode_targets(page_table, lengths, write_mask,
                               pools[0].shape[2])
    attend = _decode_attend(cfg, interpret, None)
    work = decode_work(page_table, lengths + 1)

    h = _embed_scale(embed_lookup(params["embed"], input_ids,
                                  params["final_norm"].dtype), cfg)

    def layer_body(carry, xs):
        h, k_pool, v_pool, ssm, conv = carry
        lp, layer = xs
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q, kproj, vproj = _qkv_proj(lp, _attn_in(x, cfg), cfg, positions,
                                    cos_t, sin_t)
        k_pool = k_pool.at[layer, pid, off].set(
            kproj.reshape(B, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, pid, off].set(
            vproj.reshape(B, -1).astype(v_pool.dtype))
        attn = attend(q[:, 0], k_pool, v_pool, work, layer)

        z, u, dt = _mixer_in(lp, x, cfg)
        y, ssm, conv = _mixer_step(lp, layer, cfg, u[:, 0], dt[:, 0], ssm,
                                   conv, write_mask, h.dtype, not interpret)
        o_s = _mixer_out(lp, y[:, None], z, cfg, h.dtype)

        h = _attn_out(lp, h, attn.reshape(B, 1, Hq * D),
                      cfg.attention_out_multiplier) + o_s
        h = _mlp_residual(lp, h, cfg)
        return (h, k_pool, v_pool, ssm, conv), None

    (h, k_pool, v_pool, ssm, conv), _ = jax.lax.scan(
        layer_body, (h, *pools, state["ssm"], state["conv"]),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, (k_pool, v_pool), {"ssm": ssm, "conv": conv}


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
) -> tuple[jnp.ndarray, PagedPools, State]:
    """One ragged mixed step over the tokens it has. Returns (hidden, pools,
    state); lanes, the decode group, K/V and ``hidden`` as
    ``llama.forward_paged_mixed``. A lane's slot advances its recurrent state
    by the lane's ``q_len`` tokens through the chunked form, from the zero
    state where its history is 0 and from its slab row otherwise, and only
    the lanes' rows are read and written; a lane with ``q_len`` 0 or
    ``write_mask`` False keeps state and conv tail bit for bit. A decode
    group's rows advance by one token as in :func:`forward_paged_decode`.
    Every other row of the slab is not touched."""
    interpret = _one_device(mesh, interpret)
    cos_t, sin_t = rope_tables
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

    def layer_body(carry, xs):
        h, k_pool, v_pool, ssm, conv = carry
        lp, layer = xs
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q, kproj, vproj = _qkv_proj(lp, _attn_in(x, cfg), cfg, lay.positions,
                                    cos_t, sin_t)
        n = lay.pid.shape[0]
        k_pool = k_pool.at[layer, lay.pid, lay.off].set(
            kproj.reshape(n, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[layer, lay.pid, lay.off].set(
            vproj.reshape(n, -1).astype(v_pool.dtype))
        attn = mixed_attention(lay, q, k_pool, v_pool, hist, q_lens, layer,
                               lane_attend, decode_attend)

        # the mixer: one input and one output projection over all tokens,
        # split only around the recurrence — the decode group's step first,
        # then the lanes' chunk on their own rows of the slab
        z, u, dt = _mixer_in(lp, x, cfg)
        ys = []
        if nd:
            y_dec, ssm, conv = _mixer_step(
                lp, layer, cfg, u[0, :nd], dt[0, :nd], ssm, conv, decode.run,
                h.dtype, not interpret)
            ys.append(y_dec)
        y, ssm, conv = _mixer_chunk(
            lp, layer, cfg, u[0, nd:].reshape(R, Qc, -1),
            dt[0, nd:].reshape(R, Qc, -1), ssm, conv, rows, hist == 0,
            advance, span, h.dtype)
        ys.append(y.reshape(R * Qc, -1))
        o_s = _mixer_out(lp, jnp.concatenate(ys)[None], z, cfg, h.dtype)

        h = _attn_out(lp, h, attn, cfg.attention_out_multiplier) + o_s
        h = _mlp_residual(lp, h, cfg)
        return (h, k_pool, v_pool, ssm, conv), None

    (h, k_pool, v_pool, ssm, conv), _ = jax.lax.scan(
        layer_body, (h, *pools, state["ssm"], state["conv"]),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h = mixed_hidden_out(lay, h, q_lens, rows)
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return h, (k_pool, v_pool), {"ssm": ssm, "conv": conv}
