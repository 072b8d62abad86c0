"""Pallas attention over LATENT pages (multi-head latent attention, absorbed).

A latent page holds, a token, one row of ``rank + rope`` numbers: the
compressed key/value ``c`` (``kv_lora_rank``) and the one rotary key ``k_r``
every head shares. There is no kv-head axis and no value pool: with the up
projections absorbed into the query and the output (``models/kimi_k2.py``),
head ``h``'s score against token ``s`` is ``q~_h . [c(s) | k_r(s)]`` and its
output is ``sum_s p c(s)``, so a page is read ONCE and used as the key (all
its lanes) and as the value (its first ``rank`` lanes), by every head.

Both kernels take the STACKED pool as the pool keeps it, ``[L, N, page,
lanes]`` (``rank + rope`` numbers in whole lane tiles, the rest zero, in the
queries too), and the layer as a scalar-prefetch operand, like
``ops/paged_attention.py``'s. The decode kernel walks that file's work list
of the pages in use (``decode_work_list``, PR 32: the pages ``page_span``
gives, slots in order, no program for a slot of the table that holds
nothing), a slot's pages ``PAGE_GROUP`` at a time. The queries arrive HEAD-MAJOR and already absorbed, and the softmax
scale is the caller's (it carries YaRN's ``mscale^2``).

**A window** (``sliding_window``, static; ``models/motif.py``'s window
layers): a query at ``t`` sees the keys ``t - window < s <= t``. Both kernels
start a row at the first page of that span (``paged_attention._span_first``)
and mask the rest, so the programs a row costs do not grow with its length,
and the pages left of the span are never read: the pool may have given them
to another row. Without a window both are what they were, bit for bit.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _LANES, _NEG_INF, _span_first, page_span

_VMEM_LIMIT = 64 * 1024 * 1024


def _online_softmax_step(scores, mask, value, acc_ref, m_ref, l_ref):
    """One block of keys of the flash recurrence: ``scores`` [R, keys] f32
    (already scaled), ``mask`` its visible keys, ``value`` [keys, rank] the
    first ``rank`` lanes of the same latent rows."""
    scores = jnp.where(mask, scores, _NEG_INF)
    m_prev = m_ref[...]
    m_blk = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jax.lax.broadcast_in_dim(
        m_blk, m_prev.shape, (0, 1)))
    m_ref[...] = m_new
    # a row with no visible key yet sits at the floor: it carries no mass
    correction = jnp.where(m_new > _NEG_INF * 0.5,
                           jnp.exp(m_prev - m_new), 0.0)
    p = jnp.where(mask, jnp.exp(scores - m_new[:, :1]), 0.0)
    l_blk = jnp.sum(p, axis=1, keepdims=True)
    l_ref[...] = l_ref[...] * correction + jax.lax.broadcast_in_dim(
        l_blk, m_prev.shape, (0, 1))
    pv = jax.lax.dot_general(p.astype(value.dtype), value,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * correction[:, :1] + pv


#: latent pages one program of the decode kernel takes. A page's body is two
#: small dots and a softmax update, and a grid step costs about what that
#: body does (PERF.md, PR 33: 0.66 us a page at one page a program, 0.10 us of
#: it DMA), so a program takes a GROUP of a row's pages: as many DMAs, one
#: dot over all their keys, one update of the accumulator
PAGE_GROUP = 8


class LatentWork(NamedTuple):
    """The latent decode kernel's grid, flattened: ``decode_work_list``'s
    list of the pages in use (slots in order, a slot's pages ascending, every
    slot at least one item, an empty slot's computing nothing) with a slot's
    pages taken ``group`` at a time. The arrays are as long as a full table
    needs; the grid runs the first ``n_items``."""
    row: jnp.ndarray       # [N] int32 the item's slot
    first: jnp.ndarray     # [N] int32 the first logical page of its group
    phys: jnp.ndarray      # [N*group] int32 its pages (past the slot's last
    #                        page: that page again, masked by the length)
    lengths: jnp.ndarray   # [B] int32 valid length (incl. current token)
    n_items: jnp.ndarray   # [] int32 items in use: the grid's bound


def latent_work_list(page_table: jnp.ndarray, lengths: jnp.ndarray,
                     page_size: int, group: int = PAGE_GROUP,
                     sliding_window: int | None = None) -> LatentWork:
    """The work list of one decode step over latent pages, from
    ``page_table`` [B, Pmax] and ``lengths`` [B] (incl. the current token);
    the same for every layer of one window, so it is built once a step,
    outside the scan over layers. The pages it names are those ``page_span``
    gives: what the scheduler's walked/offered counters count."""
    B, Pmax = page_table.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    start, last = page_span(lengths, page_size, Pmax, sliding_window)
    span = last if sliding_window is None else last - start
    groups = span // group + 1                              # [B], >= 1
    ends = jnp.cumsum(groups)
    item = jnp.arange(B * -(-Pmax // group), dtype=jnp.int32)
    row = jnp.minimum(
        jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        B - 1)
    first = jnp.minimum(item - (ends - groups)[row], groups[row] - 1) * group
    if sliding_window is not None:
        first = first + start[row]
    pages = jnp.minimum(
        first[:, None] + jnp.arange(group, dtype=jnp.int32)[None, :],
        last[row][:, None])
    phys = jnp.asarray(page_table, jnp.int32)[row[:, None], pages]
    return LatentWork(row, first, phys.reshape(-1), lengths, ends[-1])


def _decode_kernel(row_ref, first_ref, phys_ref, len_ref, layer_ref, q_ref,
                   *rest, page_size: int, n_pages: int, rank: int,
                   scale: float, group: int, sliding_window: int | None):
    """One work item: ``group`` latent pages of one slot. q_ref [1, Hq,
    lanes]; the ``group`` page refs [1, 1, page, lanes] each; o_ref [1, Hq,
    rank]; acc [Hq, rank] f32; m/l [Hq, LANES] f32."""
    pages, (o_ref, acc_ref, m_ref, l_ref) = rest[:group], rest[group:]
    i = pl.program_id(0)
    first = first_ref[i]
    length = len_ref[row_ref[i]]
    start, last = page_span(length, page_size, n_pages, sliding_window)

    @pl.when(first == start)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = first * page_size

    @pl.when(k_start < length)      # every item but an empty slot's
    def _compute():
        # the group's rows as ONE key/value block: a page is read once and
        # is the key (all its lanes) and the value (its first ``rank``)
        rows = jnp.concatenate([c[0, 0] for c in pages], axis=0) \
            if group > 1 else pages[0][0, 0]
        scores = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [Hq, G*page]
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        mask = k_pos < length
        if sliding_window is not None:      # the query sits at length - 1
            mask &= k_pos >= length - sliding_window
        _online_softmax_step(scores, mask, rows[:, :rank],
                             acc_ref, m_ref, l_ref)

    @pl.when(first + group > last)
    def _finalize():
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret",
                                             "sliding_window", "name"))
def mla_decode_attention(
    q: jnp.ndarray,           # [B, Hq, lanes] absorbed query, one a slot
    pool: jnp.ndarray,        # [L, N, page, lanes] the stacked latent pool
    work: LatentWork,         # latent_work_list(page_table, lengths, page)
    layer: jnp.ndarray | int = 0,
    *,
    rank: int,
    scale: float,
    interpret: bool = False,
    sliding_window: int | None = None,   # the one ``work`` was built with
    name: str | None = None,             # the call site's, in a device trace
) -> jnp.ndarray:
    """Returns ``[B, Hq, rank]``: each head's softmax-weighted sum of the
    compressed rows of its slot's pages in layer ``layer`` (the caller
    applies ``W_uv``)."""
    B, Hq, width = q.shape
    _, _, page_size, _ = pool.shape
    group = work.phys.shape[0] // work.row.shape[0]

    def page_spec(g: int) -> pl.BlockSpec:
        return pl.BlockSpec(
            (1, 1, page_size, width),
            lambda i, row, first, phys, ln, ly: (ly[0], phys[i * group + g],
                                                 0, 0))

    def at_row(i, row, first, phys, ln, ly):
        return (row[i], 0, 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size,
                          n_pages=-(-work.row.shape[0] // B) * group,
                          rank=rank, scale=scale, group=group,
                          sliding_window=sliding_window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(work.n_items,),
            in_specs=[pl.BlockSpec((1, Hq, width), at_row),
                      *(page_spec(g) for g in range(group))],
            out_specs=pl.BlockSpec((1, Hq, rank), at_row),
            scratch_shapes=[pltpu.VMEM((Hq, rank), jnp.float32),
                            pltpu.VMEM((Hq, _LANES), jnp.float32),
                            pltpu.VMEM((Hq, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(work.row, work.first, work.phys, work.lengths,
      jnp.asarray(layer, jnp.int32).reshape(1), q, *([pool] * group))


def _ragged_kernel(pt_ref, hist_ref, qlen_ref, layer_ref, q_ref, c_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, page_size: int, q_block: int,
                   rank: int, scale: float, sliding_window: int | None):
    """One (lane, q-block, page) program. q_ref [1, Hq, Qb, rank+rope],
    head-major, so its rows flatten to ``r = h*Qb + qi`` for nothing; the
    query at ``qi`` sits at ``hist + q0 + qi`` and sees the keys up to
    itself (the last ``sliding_window`` of them, the page axis then starting
    at the page of the block's first query's first key)."""
    b = pl.program_id(0)
    qb = pl.program_id(1)
    j = pl.program_id(2)
    hist, qlen = hist_ref[b], qlen_ref[b]
    q0 = qb * q_block
    Hq = q_ref.shape[1]
    R = Hq * q_block

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = _ragged_page(j, hist, q0, page_size, sliding_window) * page_size
    q_hi = hist + jnp.minimum(qlen, q0 + q_block) - 1

    @pl.when(jnp.logical_and(q0 < qlen, k_start <= q_hi))
    def _compute():
        q = q_ref[0].reshape(R, q_ref.shape[3])
        scores = jax.lax.dot_general(
            q, c_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [R, page]
        q_idx = q0 + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0) % q_block
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        mask = (q_idx < qlen) & (k_pos <= hist + q_idx)
        if sliding_window is not None:
            mask &= k_pos > hist + q_idx - sliding_window
        _online_softmax_step(scores, mask, c_ref[0, 0, :, :rank], acc_ref,
                             m_ref, l_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).reshape(
            Hq, q_block, rank).astype(o_ref.dtype)


def _ragged_page(j, hist, q0, page_size: int, sliding_window: int | None):
    """The logical page program ``j`` of a q-block whose first query sits at
    ``hist + q0`` reads: ``j`` itself, or under a window ``j`` pages past the
    first page that query sees (:func:`_span_first`: its length is its
    position + 1)."""
    if sliding_window is None:
        return j
    return j + _span_first(hist + q0 + 1, page_size, hist + q0 + 1,
                           sliding_window)


def ragged_q_block(width: int) -> int:
    """Queries a program of the ragged kernel takes: whole sublane tiles of
    a 16-bit block, and as many as keep the accumulator a few MB."""
    return min(32, width)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret",
                                             "sliding_window", "name"))
def mla_ragged_attention(
    q: jnp.ndarray,           # [R, Hq, Qc, rank+rope] absorbed, head-major
    pool: jnp.ndarray,        # [L, N, page, rank+rope]
    page_table: jnp.ndarray,  # [R, Pmax] the lanes' rows of the page table
    hist: jnp.ndarray,        # [R] tokens BEFORE each lane's span
    q_lens: jnp.ndarray,      # [R] span length (0 = idle lane)
    layer: jnp.ndarray | int = 0,
    *,
    rank: int,
    scale: float,
    interpret: bool = False,
    sliding_window: int | None = None,
    name: str | None = None,
) -> jnp.ndarray:
    """A prompt's chunk over latent pages, absorbed like the decode kernel:
    each lane's span of ``q_lens`` queries attends causally over its own
    history and the span itself, which the caller has already written to
    the pool. Returns ``[R, Hq, Qc, rank]``; positions past a lane's span
    are zeros. Under a window the page axis of the grid is the pages a
    q-block's windows can span, not the table's."""
    R, Hq, Qc, width = q.shape
    _, _, page_size, _ = pool.shape
    Pmax = page_table.shape[1]
    q_block = ragged_q_block(Qc)
    if Qc % q_block or q_block % 16:
        raise ValueError(f"a chunk of {Qc} queries is not whole blocks of "
                         f"{q_block} (multiples of 16)")
    n_pages = Pmax if sliding_window is None else min(
        Pmax, (sliding_window + q_block - 3) // page_size + 2)

    def page_index(b, qb, j, pt_ref, hist_ref, qlen_ref, layer_ref):
        # clamp j into the pages this (lane, q-block) sees, so that skipped
        # programs revisit the resident page and their DMA is elided
        q_hi = hist_ref[b] + jnp.minimum(qlen_ref[b], (qb + 1) * q_block) - 1
        jj = jnp.minimum(
            _ragged_page(j, hist_ref[b], qb * q_block, page_size,
                         sliding_window),
            jnp.maximum(q_hi // page_size, 0))
        return (layer_ref[0], pt_ref[b, jj], 0, 0)

    def q_index(b, qb, j, *_):
        return (b, 0, qb, 0)

    rows = Hq * q_block
    return pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size,
                          q_block=q_block, rank=rank, scale=scale,
                          sliding_window=sliding_window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(R, Qc // q_block, n_pages),
            in_specs=[pl.BlockSpec((1, Hq, q_block, width), q_index),
                      pl.BlockSpec((1, 1, page_size, width), page_index)],
            out_specs=pl.BlockSpec((1, Hq, q_block, rank), q_index),
            scratch_shapes=[pltpu.VMEM((rows, rank), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, Hq, Qc, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), hist.astype(jnp.int32),
      q_lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, pool)
