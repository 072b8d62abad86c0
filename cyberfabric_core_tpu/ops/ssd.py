"""The Mamba-2 (SSD) recurrence of a state-space mixer, in the two forms the
serving programs need.

Per head (``P`` channels, state size ``N``; B and C shared by the heads of a
group), with ``Δ_t = softplus(dt_t + dt_bias)`` and ``A < 0``::

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ        S: [P, N]
    y_t = S_t C_t + D x_t

- :func:`ssd_chunked` — one prefill chunk of a mixed step: the same recurrence
  as matmuls over chunks of ``chunk`` tokens (the published
  ``mamba_chunk_size``), each row from its own incoming state, ragged by
  ``q_lens``: Δ is zeroed beyond a row's ``q_len``, so a row's outgoing state
  is the state after its ``q_len``-th token whatever the padded width.
- :func:`ssm_state_update` — one decode step as ONE Pallas kernel that reads
  and writes each row's ``[H, P, N]`` f32 state where the slab keeps it:
  the kernel takes the stacked slab ``[L, rows, H, P, N]`` whole, the layer is
  a scalar-prefetch operand of the index map and the slab is aliased to the
  output, so nothing slab-sized is sliced or copied in front of the call
  (PERF.md section 6, PR 25: a Mosaic call handed ``slab[layer]`` makes XLA
  materialise that layer first). A program takes 1 MB of heads
  (``_head_block``: whole groups where several fit), updates them on the VPU
  and sums the read-out ``S' C`` over the state axis on the MXU
  (``_row_sums``: three bfloat16 pieces of each f32 product against ones, an
  f32 sum in another order): summed over the lanes on the XLU a call took
  980 us at granite's ``[64, 128]`` heads where a program that only copies
  its block takes 835, and a fold of rotates and selects took 1 788
  (PERF.md section 5, PR 46). A plain ``jax.numpy`` step with the same
  arithmetic serves the CPU (tests, rehearsals).
- :func:`causal_conv` / :func:`causal_conv_step` — the depthwise conv in
  front of the recurrence, carrying each row's tail of ``K - 1`` inputs.

State and conv tails are f32; x, B and C arrive in the activation dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ------------------------------------------------------------------ the conv
def causal_conv(u: jnp.ndarray, tail: jnp.ndarray, weight: jnp.ndarray,
                bias: jnp.ndarray, q_lens: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv over a chunk. ``u`` [B, T, C] f32 inputs, ``tail``
    [B, K-1, C] each row's last K-1 inputs before the chunk, ``weight`` [K, C]
    (tap K-1 multiplies the current input), ``bias`` [C]. Returns the conv
    output [B, T, C] (before the activation) and each row's new tail: its
    last K-1 inputs after ``q_len`` tokens — the old tail, bit for bit, where
    ``q_len`` is 0."""
    K = weight.shape[0]
    T = u.shape[1]
    seq = jnp.concatenate([tail, u], axis=1)                  # [B, K-1+T, C]
    out = bias + sum(seq[:, k: k + T] * weight[k] for k in range(K))
    # the K-1 inputs that end at token q_len: rows q_len .. q_len+K-2 of seq
    idx = q_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
    return out, new_tail


def causal_conv_step(u: jnp.ndarray, tail: jnp.ndarray, weight: jnp.ndarray,
                     bias: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token: ``u`` [B, C], ``tail`` [B, K-1, C]. Returns the conv output
    [B, C] and the shifted tail."""
    seq = jnp.concatenate([tail, u[:, None]], axis=1)         # [B, K, C]
    return bias + jnp.einsum("bkc,kc->bc", seq, weight), seq[:, 1:]


# ------------------------------------------------------- the chunked recurrence
def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                b_mat: jnp.ndarray, c_mat: jnp.ndarray, d_skip: jnp.ndarray,
                state: jnp.ndarray, q_lens: jnp.ndarray, chunk: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``x`` [B, T, H, P], ``dt`` [B, T, H] f32 (after softplus), ``a`` [H]
    f32 negative, ``b_mat`` / ``c_mat`` [B, T, G, N], ``d_skip`` [H] f32,
    ``state`` [B, H, P, N] f32 each row's incoming state, ``q_lens`` [B].
    Returns ``y`` [B, T, H, P] f32 and the outgoing state [B, H, P, N] f32.

    Within a chunk of ``L`` tokens, with ``s_i = Σ_{k≤i} Δ_k A``:
    ``y_i = Σ_{j≤i} (C_i·B_j) exp(s_i − s_j) Δ_j x_j  +  exp(s_i) C_i S_in``
    and ``S_out = exp(s_L) S_in + Σ_j exp(s_L − s_j) Δ_j x_j B_jᵀ``; chunks
    follow one another under ``lax.scan``, so the live temporaries are one
    chunk's. Matmul operands are in the activation dtype with f32
    accumulation, decays and sums in f32."""
    B, T, H, P = x.shape
    G, N = b_mat.shape[2], b_mat.shape[3]
    L = min(chunk, T)
    width = T
    if T % L:           # pad to whole chunks; Δ is 0 there, like past q_len
        pad = [(0, 0), (0, L - T % L)]
        x, dt, b_mat, c_mat = (jnp.pad(t, pad + [(0, 0)] * (t.ndim - 2))
                               for t in (x, dt, b_mat, c_mat))
        T = x.shape[1]
    n_chunks = T // L
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < q_lens[:, None]
    dt = jnp.where(valid[:, :, None], dt, 0.0)
    act = x.dtype

    def chunks(t):      # [B, T, ...] -> [n_chunks, B, L, ...]
        return jnp.moveaxis(t.reshape(B, n_chunks, L, *t.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((L, L), bool))

    def one_chunk(s_in, xs):
        xc, dtc, bc, cc = xs                    # [B,L,H,P] [B,L,H] [B,L,G,N] x2
        s = jnp.cumsum(dtc * a, axis=1)                           # [B, L, H]
        # decay from token j to token i (i >= j); masked BEFORE the exp so
        # the upper triangle never overflows
        seg = s[:, :, None, :] - s[:, None, :, :]                 # [B, i, j, H]
        decay = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bgij", cc, bc,
                        preferred_element_type=jnp.float32)       # [B, G, L, L]
        cb = jnp.repeat(cb, H // G, axis=1)                       # [B, H, L, L]
        w = cb * jnp.moveaxis(decay, 3, 1) * jnp.moveaxis(dtc, 2, 1)[:, :, None, :]
        y = jnp.einsum("bhij,bjhp->bihp", w.astype(act), xc,
                       preferred_element_type=jnp.float32)
        # what the incoming state adds: exp(s_i) C_i S_in
        ch = jnp.repeat(cc, H // G, axis=2)                       # [B, L, H, N]
        y = y + jnp.exp(s)[..., None] * jnp.einsum(
            "bihn,bhpn->bihp", ch.astype(jnp.float32), s_in,
            preferred_element_type=jnp.float32)
        # the chunk's own contribution to the outgoing state
        to_end = jnp.exp(s[:, -1:, :] - s) * dtc                  # [B, L, H]
        bh = jnp.repeat(bc, H // G, axis=2)                       # [B, L, H, N]
        xw = (xc.astype(jnp.float32) * to_end[..., None]).astype(act)
        s_out = (jnp.exp(s[:, -1, :])[:, :, None, None] * s_in
                 + jnp.einsum("bjhp,bjhn->bhpn", xw, bh,
                              preferred_element_type=jnp.float32))
        return s_out, y

    s_out, ys = jax.lax.scan(one_chunk, state,
                             (chunks(x), chunks(dt), chunks(b_mat),
                              chunks(c_mat)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, H, P)
    y = (y + d_skip[None, None, :, None] * x.astype(jnp.float32))[:, :width]
    # a row that consumed nothing hands its state back bit for bit
    s_out = jnp.where((q_lens > 0)[:, None, None, None], s_out, state)
    return y, s_out


# ------------------------------------------------------------ the decode step
#: f32 state bytes one kernel program should move each way: what falcon-h1's
#: 8 heads of [128, 256] are. A program that only copies its block moves a
#: call's 537 MB in 847 / 835 / 841 us at 512 KB / 1 MB / 2 MB (my chip runs,
#: PR 46: 78.6% of 819 GB/s at 1 MB, the floor of this tiling whatever the
#: body computes), so a row of many small heads (granite: 128 heads of
#: [64, 128], 32 KB each) takes several to a program
_STATE_BLOCK_BYTES = 1024 * 1024


def _head_block(heads: int, groups: int, head_bytes: int) -> int:
    """Heads a kernel program handles: the most whose f32 state
    (``head_bytes`` each) stays within ``_STATE_BLOCK_BYTES``, as a divisor
    of one group's heads where a group is more than that (8 at falcon-h1's
    [128, 256], 32 at granite's [64, 128], 16 at solar-open2's [128, 128])
    and as WHOLE groups where several fit (nemotron: two groups of 16)."""
    per_group = heads // groups
    fit = max(1, _STATE_BLOCK_BYTES // head_bytes)
    if fit >= per_group:
        whole = min(groups, fit // per_group)
        while groups % whole:
            whole -= 1
        return whole * per_group
    while per_group % fit:
        fit -= 1
    return fit


def _row_sums(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Each row's sum over the lanes of ``x`` [P, N] f32, as [P, width] with
    the sum in every lane, on the MXU. ``x`` is split into three bfloat16
    pieces that add up to it (8 + 8 + 8 bits of its 24) and each is
    multiplied against a block of ones with f32 accumulation: the products
    are exact, so this is the f32 sum in another order (on the chip 1.5 ulp
    from the VPU's; two pieces read 1e-4 of a sum of 32). The lane reduction
    it replaces cost the XLU 15 ns a vreg of state where the vreg's DMA
    takes 9.8 (PERF.md section 5, PR 46)."""
    ones = jnp.ones((x.shape[1], width), jnp.bfloat16)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(piece):
        return jnp.dot(piece, ones, preferred_element_type=jnp.float32)

    return dot(hi) + dot(mid) + dot(lo)


def _state_update_kernel(layer_ref, mask_ref, s_ref, xdt_ref, da_ref, b_ref,
                         c_ref, s_out_ref, y_ref, *, hb: int, per_group: int):
    """One (row, head block): S' = dA S + (Δx) Bᵀ, y = S' C. The state block
    is [hb, P, N] with N on the lanes; Δx arrives as columns [P, hb] so that a
    head's x broadcasts along the lanes and B, C (a row for each group the
    block holds) along the sublanes: no transpose in the kernel. The update
    is the VPU's, in f32; the read-out's sums over N are the MXU's
    (``_row_sums``), which the update leaves idle."""
    del layer_ref                                   # used by the index maps
    keep = mask_ref[pl.program_id(0)] != 0
    xdt, da = xdt_ref[0, 0], da_ref[0, 0]           # [P, hb], [1, hb]
    lane = jax.lax.broadcasted_iota(jnp.int32, xdt.shape, 1)
    width = -(-hb // 128) * 128                     # hb in whole lane tiles
    y = jnp.zeros(xdt.shape, jnp.float32)
    for j in range(hb):
        b_row, c_row = b_ref[0, j // per_group], c_ref[0, j // per_group]
        s = s_ref[0, 0, j]                          # [P, N]
        new = s * da[:, j: j + 1] + xdt[:, j: j + 1] * b_row
        y = jnp.where(lane == j, _row_sums(new * c_row, width)[:, :hb], y)
        s_out_ref[0, 0, j] = jnp.where(keep, new, s)
    y_ref[0, 0] = y


def _state_update_pallas(ssm, layer, xdt, da, b_mat, c_mat, write_mask, *,
                         interpret: bool):
    _, _, H, P, N = ssm.shape
    B, G = xdt.shape[0], b_mat.shape[1]
    per_group = H // G
    hb = _head_block(H, G, 4 * P * N)
    nhb = H // hb
    span = max(hb, per_group)           # heads whose B and C one block holds

    def cols(t):        # [B, H, P] -> [B, nhb, P, hb]: a head's values a column
        return t.reshape(B, nhb, hb, P).transpose(0, 1, 3, 2)

    def group_block(b, h, layer, mask):
        return (b, h * hb // span, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nhb),
        in_specs=[
            pl.BlockSpec((1, 1, hb, P, N),
                         lambda b, h, layer, mask: (layer[0], b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, hb), lambda b, h, layer, mask: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, hb), lambda b, h, layer, mask: (b, h, 0, 0)),
            pl.BlockSpec((1, span // per_group, 1, N), group_block),
            pl.BlockSpec((1, span // per_group, 1, N), group_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, P, N),
                         lambda b, h, layer, mask: (layer[0], b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, hb), lambda b, h, layer, mask: (b, h, 0, 0)),
        ])
    ssm, y = pl.pallas_call(
        functools.partial(_state_update_kernel, hb=hb, per_group=per_group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((B, nhb, P, hb), jnp.float32)],
        # operand 2 (after the two scalar-prefetch operands) is the slab: the
        # kernel writes the rows it read, in place
        input_output_aliases={2: 0},
        interpret=interpret,
        name="ssm_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      write_mask.astype(jnp.int32), ssm, cols(xdt),
      da.reshape(B, nhb, 1, hb), b_mat[:, :, None, :].astype(jnp.float32),
      c_mat[:, :, None, :].astype(jnp.float32))
    return y.transpose(0, 1, 3, 2).reshape(B, H, P), ssm


def _state_update_jnp(ssm, layer, xdt, da, b_mat, c_mat, write_mask):
    """The kernel's arithmetic in plain ``jax.numpy`` (CPU)."""
    B, H = xdt.shape[0], xdt.shape[1]
    rep = H // b_mat.shape[1]
    old = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)[:B]
    bh = jnp.repeat(b_mat.astype(jnp.float32), rep, axis=1)     # [B, H, N]
    ch = jnp.repeat(c_mat.astype(jnp.float32), rep, axis=1)
    new = old * da[:, :, None, None] + xdt[..., None] * bh[:, :, None, :]
    y = jnp.sum(new * ch[:, :, None, :], axis=-1)
    new = jnp.where(write_mask[:, None, None, None], new, old)
    ssm = jax.lax.dynamic_update_slice(
        ssm, new[None], (layer, 0, 0, 0, 0))
    return y, ssm


def ssm_state_update(ssm: jnp.ndarray, layer: jnp.ndarray, x: jnp.ndarray,
                     dt: jnp.ndarray, a: jnp.ndarray, b_mat: jnp.ndarray,
                     c_mat: jnp.ndarray, write_mask: jnp.ndarray, *,
                     kernel: bool, interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token of every row. ``ssm`` is the stacked slab [L, rows, H, P, N]
    f32 (rows >= B: row b of the batch is row b of the slab), ``layer`` a
    scalar, ``x`` [B, H, P], ``dt`` [B, H] f32 after softplus, ``a`` [H],
    ``b_mat`` / ``c_mat`` [B, G, N], ``write_mask`` [B] bool: a row marked
    False keeps its state bit for bit. Returns ``y`` [B, H, P] f32 (without
    the D skip) and the slab. ``kernel`` picks the Pallas kernel (the chip;
    ``interpret`` for a test of it on the CPU) over the ``jax.numpy`` step."""
    xdt = x.astype(jnp.float32) * dt[..., None]
    da = jnp.exp(dt * a)
    if kernel:
        return _state_update_pallas(ssm, layer, xdt, da, b_mat, c_mat,
                                    write_mask, interpret=interpret)
    return _state_update_jnp(ssm, layer, xdt, da, b_mat, c_mat, write_mask)
