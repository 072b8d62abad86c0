"""Pallas streaming flash-attention kernel for prefill self-attention.

True flash attention (Dao et al. style, TPU-shaped): the grid walks
(batch, q-head, q-block, kv-block); K/V stream through VMEM one
[BLOCK_K, D] tile at a time while per-q-block online-softmax state
(m, l, acc — f32) persists in VMEM scratch across the kv-block axis
(sequentially iterated on TPU). No [BQ, S] score tile and no full-S
K/V resident ever exist, so VMEM is O(BQ·D + BK·D + BQ·BK) regardless
of sequence length — 32k+ prefill fits on one chip.

GQA is expressed in the k/v index_map (q head h reads kv head h//G) — no
materialized head repetition in HBM. Causal structure is exploited twice:
kv-blocks strictly in the future of a q-block are masked off cheaply inside
the kernel via @pl.when (no MXU work), and the within-diagonal-block mask is
the usual position compare.

Decode (T=1) stays on the jnp/paged path — it is HBM-bound on the cache read
and gains nothing from this tiling. Falls back to interpret mode off-TPU so
CPU tests exercise the same kernel code.

Reference parity note: the reference (cyberfabric/cyberfabric-core) has no
on-device attention at all (SURVEY §2.6 — inference is delegated to external
providers); this kernel is part of the TPU-first additions that make the
llm-gateway's local worker real.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128  # f32 lane width; m/l scratch is lane-replicated


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, block_q: int, block_k: int,
                  sliding_window: int | None = None):
    """One (batch, q_head, q_block, kv_block) program.

    Refs:
      len_ref: [B] int32 in SMEM (scalar prefetch) — valid length per row. A
               blocked (1,)-of-[B] SMEM operand only lowers at B == 1
      q_ref:   [1, 1, BQ, D]; k_ref/v_ref: [1, 1, BK, D]; o_ref: [1, 1, BQ, D]
      acc_ref: [BQ, D] f32 scratch; m_ref/l_ref: [BQ, LANES] f32 scratch
    """
    valid_len = len_ref[pl.program_id(0)]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: this kv block contributes only if it starts at or before the last
    # query position of the q block AND inside the row's valid length; with a
    # sliding window it must also end after the window's left edge for the
    # *first* query row.
    relevant = jnp.logical_and(
        jnp.logical_and(k_start <= q_start + block_q - 1,
                        k_start < valid_len),
        q_start < valid_len)  # q blocks fully past valid length: zeros
    if sliding_window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - sliding_window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        v = v_ref[0, 0]

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        scores = scores * (1.0 / (q.shape[-1] ** 0.5))

        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_pos <= q_pos) & (k_pos < valid_len)
        if sliding_window is not None:
            mask = mask & (k_pos > q_pos - sliding_window)
        scores = jnp.where(mask, scores, _NEG_INF)

        m_prev = m_ref[...]                       # [BQ, LANES] (replicated)
        m_blk = jnp.max(scores, axis=1, keepdims=True)       # [BQ, 1]
        m_new = jnp.maximum(m_prev, jax.lax.broadcast_in_dim(
            m_blk, m_prev.shape, (0, 1)))
        m_ref[...] = m_new
        correction = jnp.exp(m_prev - m_new)                 # [BQ, LANES]
        p = jnp.exp(scores - m_new[:, :1])                   # [BQ, BK]
        p = jnp.where(mask, p, 0.0)
        l_blk = jnp.sum(p, axis=1, keepdims=True)            # [BQ, 1]
        l_ref[...] = l_ref[...] * correction + jax.lax.broadcast_in_dim(
            l_blk, m_prev.shape, (0, 1))
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [BQ, D]
        acc_ref[...] = acc_ref[...] * correction[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)        # [BQ, 1]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_k", "interpret", "sliding_window"))
def flash_self_attention(
    q: jnp.ndarray,        # [B, T, Hq, D]
    k: jnp.ndarray,        # [B, T, Hkv, D]
    v: jnp.ndarray,        # [B, T, Hkv, D]
    lengths: jnp.ndarray,  # [B] int32 valid lengths
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    sliding_window: int | None = None,
) -> jnp.ndarray:
    """Causal self-attention over a full prompt (prefill; no cache history)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv

    # pad T to a lane multiple so blocks stay MXU-sized even for awkward
    # sequence lengths (padded keys are masked by valid_len; padded query rows
    # are garbage and sliced off below)
    Tp = -(-T // _LANES) * _LANES
    if Tp != T:
        pad = [(0, 0), (0, Tp - T), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # normalize block params to powers of two, then shrink until they divide
    # Tp — since Tp is a multiple of 128 this floors at 128, never degenerate
    def _block(requested: int) -> int:
        b = 1
        while b * 2 <= min(requested, Tp):
            b *= 2
        while Tp % b:
            b //= 2
        return b

    bq = _block(block_q)
    bk = _block(block_k)

    # layout: heads-major so each program reads a contiguous [T, D] tile
    qh = q.transpose(0, 2, 1, 3)  # [B, Hq, Tp, D]
    kh = k.transpose(0, 2, 1, 3)  # [B, Hkv, Tp, D]
    vh = v.transpose(0, 2, 1, 3)

    def _kv_index(b, h, i, j, len_ref):
        # clamp j into the causally-relevant range for q block i so programs
        # whose body is skipped revisit the already-resident tile and Pallas
        # elides the HBM→VMEM copy (cuts ~half the KV reads; far more with a
        # sliding window). The in-kernel `relevant` mask stays authoritative.
        hi = (i * bq + bq - 1) // bk
        jj = jnp.minimum(j, hi)
        if sliding_window is not None:
            lo = jnp.maximum((i * bq - sliding_window + 1) // bk, 0)
            jj = jnp.maximum(jj, lo)
        return (b, h // G, jj, 0)

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j, len_ref: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), _kv_index)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=bq, block_k=bk,
                          sliding_window=sliding_window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, Tp // bq, Tp // bk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qh, kh, vh)
    out = out.transpose(0, 2, 1, 3)  # back to [B, Tp, Hq, D]
    return out[:, :T] if Tp != T else out


def flash_available() -> bool:
    from .platform import default_interpret

    return not default_interpret()
