"""The gated delta rule with a decay for every key channel (KDA: Kimi Delta
Attention, arXiv 2510.26692; ``fla/layers/kda.py``), in the two forms the
serving programs need.

Per head, a state ``S`` of ``[K, V]`` (``K`` key channels, ``V`` value
channels), float32. With ``α_t = exp(g_t) ∈ (0, 1)^K`` (one decay a KEY
CHANNEL, so a row of ``S``), ``β_t`` a number, ``k_t`` of unit length::

    S̃   = Diag(α_t) S_{t-1}
    S_t = S̃ + β_t k_t (v_t − S̃ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t

Mamba-2's update (``ops/ssd.py``) scales a head's whole state by ONE number
and adds an outer product; here every row has its own decay, ``S̃ᵀ k`` has to
be read off the decayed state BEFORE the rank-one correction can be written,
and ``S_tᵀ q`` off the result: two passes over a block, an update that is not
a sum of its inputs. So it is a second kernel, over the tiling rule the two
share (``ops/ssd._head_block``), not a flag on the first.

- :func:`kda_state_update` — one decode step as ONE Pallas kernel over the
  stacked slab ``[L, rows, H, K, V]``: the layer a scalar-prefetch operand of
  the index map, the slab aliased to the output, a write mask that keeps a
  row bit for bit (all as ``ssm_state_update``). A ``jax.numpy`` twin with
  the same arithmetic serves the CPU.
- :func:`kda_chunked` — a prompt's chunk in the chunked WY / UT form. Inside
  a chunk of ``C`` tokens with ``G_t = Σ_{s≤t} g_s`` and the pseudo-values
  ``u_t = β_t (v_t − S̃_tᵀ k_t)``::

      (I + Diag(β) A) U = Diag(β) (V − (K ⊙ e^G) S_0)     A strictly lower,
      A_ti = Σ_c k_t[c] k_i[c] e^{G_t[c] − G_i[c]}         unit lower-triangular
      O = (Q ⊙ e^G) S_0 + B U      B_ti = Σ_c q_t[c] k_i[c] e^{G_t[c] − G_i[c]}, i ≤ t
      S_C = Diag(e^{G_C}) S_0 + (K ⊙ e^{G_C − G})ᵀ U

  **The decay between two tokens is formed as a DIFFERENCE of cumulated
  log-decays before the exponent.** The factorised form ``(k_t ⊙ e^{G_t}) ·
  (k_i ⊙ e^{−G_i})`` overflows float32 once a chunk's cumulated log-decay
  passes −88, which seeded decays reach in a dozen tokens; every exponent
  here is ≤ 0. The price is a ``[C, C, K]`` block a head (134 MB for 64 heads
  of 128 at C = 64), so the chunks of a step follow one another under
  ``lax.scan`` and the live temporaries are one chunk's.
- :func:`kda_scan` — the recurrence itself, a token at a time: the chunked
  form's oracle on the CPU, not a served path.

State, decays and sums are float32, and the chunked form's matrix products
run at ``highest`` precision: they are a few GFLOPs a step and the triangular
system amplifies what its operands lose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssd import _head_block

#: tokens of one chunk of the WY form (flash-linear-attention's; not a key of
#: the published config)
CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------- the oracle
def kda_scan(q, k, v, g, beta, state):
    """The recurrence a token at a time. ``q``, ``k``, ``g`` [B, T, H, K],
    ``v`` [B, T, H, V], ``beta`` [B, T, H], ``state`` [B, H, K, V], all f32.
    Returns ``o`` [B, T, H, V] and the outgoing state."""
    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        r = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HIGHEST)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - r))[:, :, None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)

    state, o = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


# ------------------------------------------------------- the chunked WY form
def kda_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                g: jnp.ndarray, beta: jnp.ndarray, state: jnp.ndarray,
                q_lens: jnp.ndarray, chunk: int = CHUNK
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``q``, ``k`` [B, T, H, K] (``k`` of unit length, ``q`` scaled), ``v``
    [B, T, H, V], ``g`` [B, T, H, K] log-decays (≤ 0), ``beta`` [B, T, H],
    ``state`` [B, H, K, V] each row's incoming state, ``q_lens`` [B]; all
    f32. Returns ``o`` [B, T, H, V] and the outgoing state. Ragged by
    ``q_lens``: past a row's ``q_len`` the decay is 1 and β 0, so its
    outgoing state is the state after its ``q_len``-th token whatever the
    padded width."""
    B, T, H, K = q.shape
    C = min(chunk, T)
    width = T
    if T % C:                       # pad to whole chunks; nothing moves there
        pad = [(0, 0), (0, C - T % C)]
        q, k, v, g, beta = (jnp.pad(t, pad + [(0, 0)] * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
        T = q.shape[1]
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < q_lens[:, None]
    g = jnp.where(valid[:, :, None, None], g, 0.0)
    beta = jnp.where(valid[:, :, None], beta, 0.0)

    def chunks(t):      # [B, T, ...] -> [n_chunks, B, C, ...]
        return jnp.moveaxis(t.reshape(B, T // C, C, *t.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((C, C), bool))            # i <= t
    strict = jnp.tril(jnp.ones((C, C), bool), -1)       # i < t
    eye = jnp.eye(C, dtype=jnp.float32)

    def one_chunk(s_in, xs):
        qc, kc, vc, gc, bc = xs          # [B, C, H, K] x2, [B, C, H, V], ...
        cum = jnp.cumsum(gc, axis=1)                              # [B, C, H, K]
        # decay from token i to token t >= i, a channel: the difference
        # first, masked BEFORE the exponent so nothing above the diagonal
        # overflows
        seg = cum[:, :, None] - cum[:, None, :]               # [B, t, i, H, K]
        decay = jnp.exp(jnp.where(lower[None, :, :, None, None], seg,
                                  -jnp.inf))
        kk = jnp.sum(kc[:, :, None] * kc[:, None, :] * decay, axis=-1)
        qk = jnp.sum(qc[:, :, None] * kc[:, None, :] * decay, axis=-1)
        kk = jnp.where(strict[None, :, :, None], kk, 0.0)     # [B, t, i, H]
        system = eye + jnp.moveaxis(bc[:, :, None, :] * kk, 3, 1)  # [B,H,C,C]
        grown = jnp.exp(cum)                                      # e^{G_t}
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bthk,bhkv->bthv", kc * grown, s_in, precision=_HIGHEST))
        u = jax.scipy.linalg.solve_triangular(
            system, jnp.moveaxis(rhs, 2, 1), lower=True, unit_diagonal=True)
        o = (jnp.einsum("bthk,bhkv->bthv", qc * grown, s_in,
                        precision=_HIGHEST)
             + jnp.einsum("btih,bhiv->bthv", qk, u, precision=_HIGHEST))
        to_end = jnp.exp(cum[:, -1:] - cum)                       # [B, C, H, K]
        s_out = (jnp.exp(cum[:, -1])[..., None] * s_in
                 + jnp.einsum("bihk,bhiv->bhkv", kc * to_end, u,
                              precision=_HIGHEST))
        return s_out, o

    s_out, os_ = jax.lax.scan(
        one_chunk, state, tuple(chunks(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(os_, 0, 1).reshape(B, T, H, -1)[:, :width]
    # a row that consumed nothing hands its state back bit for bit
    s_out = jnp.where((q_lens > 0)[:, None, None, None], s_out, state)
    return o, s_out


# ------------------------------------------------------------ the decode step
def _state_update_kernel(layer_ref, mask_ref, s_ref, cols_ref, vb_ref,
                         s_out_ref, o_ref, *, hb: int):
    """One (row, head block). The state block is [hb, K, V] with V on the
    lanes; q, k, βk and the decays arrive as columns [K, 4 hb] (a head's
    values a column, so each broadcasts along the lanes) and βv as rows
    [hb, V]: the two reads ``S̃ᵀ k`` and ``Sᵀ q`` are sums over sublanes, no
    transpose, all of it on the VPU in f32."""
    del layer_ref                                   # used by the index maps
    keep = mask_ref[pl.program_id(0)] != 0
    cols, vb = cols_ref[0, 0], vb_ref[0, 0]         # [K, 4 hb], [hb, V]

    def col(part: int, j: int):
        at = part * hb + j
        return cols[:, at: at + 1]

    for j in range(hb):
        s = s_ref[0, 0, j]                          # [K, V]
        decayed = s * col(3, j)
        read = jnp.sum(decayed * col(2, j), axis=0, keepdims=True)   # [1, V]
        new = decayed + col(1, j) * (vb[j: j + 1, :] - read)
        o_ref[0, 0, j: j + 1, :] = jnp.sum(new * col(0, j), axis=0,
                                           keepdims=True)
        s_out_ref[0, 0, j] = jnp.where(keep, new, s)


def _state_update_pallas(ssm, layer, q, k, kb, alpha, vb, write_mask, *,
                         interpret: bool):
    _, _, H, K, V = ssm.shape
    B = q.shape[0]
    hb = _head_block(H, 1, 4 * K * V)
    nhb = H // hb

    def cols(t):        # [B, H, K] -> [B, nhb, K, hb]: a head's values a column
        return t.reshape(B, nhb, hb, K).transpose(0, 1, 3, 2)

    packed = jnp.concatenate([cols(t) for t in (q, k, kb, alpha)], axis=-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nhb),
        in_specs=[
            pl.BlockSpec((1, 1, hb, K, V),
                         lambda b, h, layer, mask: (layer[0], b, h, 0, 0)),
            pl.BlockSpec((1, 1, K, 4 * hb),
                         lambda b, h, layer, mask: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, hb, V), lambda b, h, layer, mask: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, K, V),
                         lambda b, h, layer, mask: (layer[0], b, h, 0, 0)),
            pl.BlockSpec((1, 1, hb, V), lambda b, h, layer, mask: (b, h, 0, 0)),
        ])
    ssm, o = pl.pallas_call(
        functools.partial(_state_update_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((B, nhb, hb, V), jnp.float32)],
        # operand 2 (after the two scalar-prefetch operands) is the slab: the
        # kernel writes the rows it read, in place
        input_output_aliases={2: 0},
        interpret=interpret,
        name="kda_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      write_mask.astype(jnp.int32), ssm, packed, vb.reshape(B, nhb, hb, V))
    return o.reshape(B, H, V), ssm


def _state_update_jnp(ssm, layer, q, k, kb, alpha, vb, write_mask):
    """The kernel's arithmetic in plain ``jax.numpy`` (CPU)."""
    B = q.shape[0]
    old = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)[:B]
    decayed = old * alpha[..., None]
    read = jnp.sum(decayed * kb[..., None], axis=2)              # [B, H, V]
    new = decayed + k[..., None] * (vb - read)[:, :, None, :]
    o = jnp.sum(new * q[..., None], axis=2)
    new = jnp.where(write_mask[:, None, None, None], new, old)
    return o, jax.lax.dynamic_update_slice(ssm, new[None], (layer, 0, 0, 0, 0))


def kda_state_update(ssm: jnp.ndarray, layer: jnp.ndarray, q: jnp.ndarray,
                     k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                     beta: jnp.ndarray, write_mask: jnp.ndarray, *,
                     kernel: bool, interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token of every row. ``ssm`` is the stacked slab [L, rows, H, K, V]
    f32 (rows >= B: row b of the batch is row b of the slab), ``layer`` a
    scalar, ``q`` and ``k`` [B, H, K] (``k`` of unit length, ``q`` scaled),
    ``v`` [B, H, V], ``g`` [B, H, K] log-decays, ``beta`` [B, H], all f32;
    ``write_mask`` [B] bool: a row marked False keeps its state bit for bit.
    Returns ``o`` [B, H, V] f32 and the slab. ``kernel`` picks the Pallas
    kernel (the chip; ``interpret`` for a test of it on the CPU) over the
    ``jax.numpy`` step."""
    kb, vb = k * beta[..., None], v * beta[..., None]
    alpha = jnp.exp(g)
    if kernel:
        return _state_update_pallas(ssm, layer, q, k, kb, alpha, vb,
                                    write_mask, interpret=interpret)
    return _state_update_jnp(ssm, layer, q, k, kb, alpha, vb, write_mask)
