"""Pallas ragged paged-attention kernel for decode (T=1) over a paged KV pool.

The decode hot loop reads each sequence's KV history through a page table
instead of a dense per-slot cache. The grid is one program for every GROUP
of consecutive pages that hold tokens a slot's query reads, and no other:

1. once a step, outside the scan over layers, ``decode_work_list`` flattens
   the slots' page spans into one list of (slot, first logical page,
   ``group`` physical pages) items, slots in order and a slot's groups
   ascending from its span's first page (a sliding window leaves a slot's
   first pages out; an empty slot keeps one item, which computes nothing and
   finalises to zeros). The list rides in scalar prefetch (SMEM) and its
   length is the grid's bound, known only when the step runs: a table of
   ``B x Pmax`` slots of which a sixth holds tokens launches a sixth of the
   programs. ``decode_page_group`` picks the group from the page's bytes
   and the query rows: 4 pages at 8 kv heads of 128, 8 at 4;
2. the pools are passed once for every page of a group, each with a
   one-page BlockSpec whose index map reads that page from the list, so the
   pipeline DMAs exactly the pages the sequences own, each once, and
   prefetches across the boundary between two slots. Where a slot's last
   group runs past its span, the spare operand names the page the same
   operand held in the item before and moves no bytes;
3. a program joins its pages' rows into ONE block of keys: one score dot,
   one mask (by position, so a spare operand's rows never count), one
   online-softmax update (f32 m/l/acc scratch) and one value dot a kv head,
   with MXU tiles that 128 and more keys fill; the accumulators are
   initialised at a slot's first group and finalised at its last.

(The ragged kernel below still launches a program for every slot of the
table and skips the ones past a row's span, their DMA elided by an index map
clamped to the last page in use: ROADMAP S18.)

Why this beats the dense path (VERDICT r1 weak #3/#6): attention reads scale
with the *tokens actually present* (sum of per-slot lengths), not
n_slots × max_seq — idle slots cost one scratch-page read, and short sequences
don't drag the whole window through HBM every step. Pages are shared
cross-request (prefix cache) with zero copies: sharing is rows in the page
table, exactly the PAPERS.md "ragged paged attention for TPU" direction.

Both kernels take the STACKED pool as the pool keeps it — ``[L, N, page,
Hkv*D]``, head-major on the merged minor axis (runtime/paged.py) — and the
layer as one more scalar-prefetch operand: a block is ``(1, 1, page, Hkv*D)``
at ``(layer, page_table[b, jj], 0, 0)``, so nothing pool-sized is sliced,
reshaped or copied in front of the call. (On a tiled TPU layout a merge of
the two minor dimensions is a physical copy, and a Mosaic call takes whole
buffers, so ``pool[layer]`` materialises: PERF.md section 6, PR 25.)

The reference has no decode path at all (inference is delegated to external
providers — SURVEY §0); this kernel is TPU-first substrate for the
llm-gateway local worker (BASELINE config #2: 64 concurrent streams).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128


def _banded_scores_2d(bands, k_of):
    """GQA scores via unrolled 2D dots — no rank-3 transpose, no batched
    dot_general (Mosaic's dot supports only 2D operands). ``bands`` is a
    list of (q_band [rows, D], kv_head) in head-major row order; ``k_of``
    maps a kv head to its [page, D] key slice — a REF-level lane slice of
    the minor-merged [1, 1, page, Hkv*D] block (the pool is stored merged):
    value-level bf16 lane slices at non-zero tile
    offsets are an unlowerable relayout, ref-level sliced LOADS are not.
    The per-band results concatenate in f32 (bf16 sublane concats are an
    unsupported multi-row shift); each output element is the same
    contraction the batched dot computes, so the results are bitwise
    identical (pinned by
    tests/test_ragged_attention.py::test_two_d_dot_rewrite_bitwise)."""
    outs = [jax.lax.dot_general(
        qb, k_of(kv), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) for qb, kv in bands]
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def _banded_weighted_v_2d(p, row_bands, v_of):
    """The p@v half of the 2D rewrite: per-band [rows, page] x [page, D]
    2D dots against ref-level lane slices of the minor-merged value block,
    concatenated (f32) back to head-major rows. ``row_bands`` lists
    (row_start, rows, kv_head); ``p`` is f32, so its sublane band slices
    lower (32-bit shifts are implemented, 16-bit are not)."""
    outs = [jax.lax.dot_general(
        p[s:s + n], v_of(kv).astype(p.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for s, n, kv in row_bands]
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def page_span(length, page_size: int, n_pages: int,
              sliding_window: int | None):
    """(first, last) logical page a decode query at position ``length - 1``
    reads, of a table of ``n_pages`` slots: up to the page of its own token,
    from the page that holds the window's first key. An empty slot has the
    span (0, 0). Scalars in the kernel, ``[B]`` arrays in the work list,
    NumPy arrays where the host counts what the grid walked."""
    last = ((length - 1) // page_size).clip(0, n_pages - 1)
    return _span_first(length, page_size, last, sliding_window), last


def _span_first(length, page_size: int, last, sliding_window: int | None):
    """The first page of the span that ends at page ``last``."""
    if sliding_window is None:
        return last * 0
    # keys <= length - 1 - window are out, so page j is in while
    # (j + 1) * page_size > length - window
    return ((length - sliding_window) // page_size).clip(0, last)


def _paged_kernel(row_ref, page_ref, phys_ref, len_ref, last_ref, layer_ref,
                  q_ref, *rest, page_size: int, group: int,
                  sliding_window: int | None = None,
                  two_d_dots: bool = False, scale: float | None = None):
    """One work item: a GROUP of consecutive pages of one slot.

    Refs:
      row_ref, page_ref: [N] int32 SMEM (scalar prefetch) — the item's slot
        and the first logical page of its group (:class:`DecodeWork`)
      phys_ref: [N*group] int32 SMEM — read by the index maps only
      len_ref: [B] int32 SMEM — valid kv length per slot (incl. current token)
      last_ref: [B] int32 SMEM — the last page of each slot's span
      layer_ref: [1] int32 SMEM — read by the index maps only
      q_ref:   [1, Hq, D] VMEM; then ``group`` key refs and ``group`` value
        refs, [1, 1, page, Hkv*D] VMEM each: the group's pages, in order
      o_ref:   [1, Hq, D] VMEM
      acc_ref: [Hq, D] f32; m_ref/l_ref: [Hq, LANES] f32

    The group's keys are ONE block of ``group * page`` rows: one score dot,
    one mask, one online-softmax update and one value dot a kv head. A
    slot's last group may hold fewer pages than ``group``; what its other
    operands hold lies past the slot's length and is masked by position.

    ``two_d_dots`` replaces the batched GQA dot_generals (and their rank-3
    operand transposes) with unrolled per-kv-head 2D dots — the form Mosaic
    can lower (its dot supports only 2D tensors); bitwise-identical to the
    batched form, which interpret mode keeps for tier-1 wall-clock.
    """
    k_refs, v_refs = rest[:group], rest[group:2 * group]
    o_ref, acc_ref, m_ref, l_ref = rest[2 * group:]
    i = pl.program_id(0)
    j = page_ref[i]
    length = len_ref[row_ref[i]]
    last = last_ref[row_ref[i]]
    keys = group * page_size

    @pl.when(j == _span_first(length, page_size, last, sliding_window))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = j * page_size

    def rows_of(refs, lanes):
        """The group's pages as one block of ``keys`` rows (a lane slice of
        each, taken at the ref: see :func:`_banded_scores_2d`; a page of 16
        rows or a multiple is whole 16-bit sublane tiles, so the join is no
        multi-row shift)."""
        parts = [r[0, 0, :, lanes] for r in refs]
        return parts[0] if group == 1 else jnp.concatenate(parts, axis=0)

    @pl.when(k_start < length)      # every item but an empty slot's
    def _compute():
        q = q_ref[0]          # [Hq, D]
        Hq, D = q.shape
        Hkv = k_refs[0].shape[3] // D
        G = Hq // Hkv
        head = lambda kv: slice(kv * D, (kv + 1) * D)  # noqa: E731

        if two_d_dots:
            # merged kv blocks ([1, 1, page, Hkv*D]): each head is a REF-level
            # lane slice. q's rows are head-major but a bf16 SUBLANE band
            # slice is itself an unlowerable multi-row shift — so each kv
            # head dots the FULL q block against its key slice and the band
            # rows are carved out of the f32 result (32-bit sublane slices
            # lower fine). The retained elements are the same contractions
            # the batched dot computes: bitwise identical, a little
            # redundant MXU work on a tiny [Hq, D] operand.
            per_head = [jax.lax.dot_general(
                q, rows_of(k_refs, head(kv)), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)[kv * G:(kv + 1) * G]
                for kv in range(Hkv)]
            scores = jnp.concatenate(per_head, axis=0) if Hkv > 1 \
                else per_head[0]                     # [Hq, keys]
        else:
            k = rows_of(k_refs, slice(None)).reshape(keys, Hkv, D)
            qg = q.reshape(Hkv, G, D)
            kt = jnp.transpose(k, (1, 2, 0))        # [Hkv, D, keys]
            scores = jax.lax.dot_general(
                qg, kt, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [Hkv, G, keys]
            scores = scores.reshape(Hq, keys)
        scores = scores * (1.0 / (D ** 0.5) if scale is None else scale)

        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (Hq, keys), 1)
        # by position: the slot's own tokens, and nothing of a page past its
        # span (a last group's spare operands; a length past the table)
        mask = k_pos < jnp.minimum(length, (last + 1) * page_size)
        if sliding_window is not None:
            mask = mask & (k_pos > length - 1 - sliding_window)
        scores = jnp.where(mask, scores, _NEG_INF)

        m_prev = m_ref[...]
        m_blk = jnp.max(scores, axis=1, keepdims=True)      # [Hq, 1]
        m_new = jnp.maximum(m_prev, jax.lax.broadcast_in_dim(
            m_blk, m_prev.shape, (0, 1)))
        m_ref[...] = m_new
        correction = jnp.exp(m_prev - m_new)                # [Hq, LANES]
        p = jnp.exp(scores - m_new[:, :1])                  # [Hq, keys]
        p = jnp.where(mask, p, 0.0)
        l_blk = jnp.sum(p, axis=1, keepdims=True)
        l_ref[...] = l_ref[...] * correction + jax.lax.broadcast_in_dim(
            l_blk, m_prev.shape, (0, 1))
        if two_d_dots:
            pv = _banded_weighted_v_2d(
                p, [(kv * G, G, kv) for kv in range(Hkv)],
                lambda kv: rows_of(v_refs, head(kv)))
        else:
            v = rows_of(v_refs, slice(None)).reshape(keys, Hkv, D)
            pg = p.reshape(Hkv, G, keys)
            vt = jnp.transpose(v, (1, 0, 2))                # [Hkv, keys, D]
            pv = jax.lax.dot_general(
                pg, vt.astype(pg.dtype), (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(Hq, D)
        acc_ref[...] = acc_ref[...] * correction[:, :1] + pv

    @pl.when(j + group > last)
    def _finalize():
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


class DecodeWork(NamedTuple):
    """The decode kernel's grid, flattened: one item for every GROUP of
    consecutive pages that hold tokens a slot's query reads. Slots in order,
    a slot's groups ascending from its span's first page, every slot at
    least one item (an empty slot's computes nothing and finalises to
    zeros). With ``N = B * ceil(Pmax / group)`` the arrays are as long as a
    full table needs; the grid runs the first ``n_items``."""
    row: jnp.ndarray       # [N] int32 the item's slot
    page: jnp.ndarray      # [N] int32 the first logical page of its group
    phys: jnp.ndarray      # [N*group] int32 the group's physical pages
    lengths: jnp.ndarray   # [B] int32 valid kv length (incl. current token)
    last: jnp.ndarray      # [B] int32 the last page of the slot's span
    n_items: jnp.ndarray   # [] int32 items in use: the grid's bound

    @property
    def group(self) -> int:
        """Pages an item takes (static: read off the arrays' shapes)."""
        return self.phys.shape[0] // self.row.shape[0]


#: K and V bytes one program should move. A program costs 0.30-0.35 us
#: before it has moved a byte (the grid step, the m/l/acc round trip, tiles
#: the MXU latches half full), what 1 MB takes to arrive four times over; a
#: larger group buys under a tenth at a full table, and where rows hold a few
#: pages it computes more keys that the mask then drops (PERF.md, PR 38)
_GROUP_BYTES = 1 << 20
#: VMEM a group may take, of the 16 MB a v5e kernel gets by default
_GROUP_VMEM = 8 << 20


def decode_page_group(page_size: int, kv_lanes: int, itemsize: int,
                      q_rows: int, n_pages: int) -> int:
    """Pages one program of the decode kernel takes: the largest power of
    two (at most 8: 16 bought under 1% at a full table in three shapes of
    four; and no more than a row of the table has) whose K and V bytes stay
    within ``_GROUP_BYTES`` and whose blocks fit ``_GROUP_VMEM``.
    ``kv_lanes`` is ``Hkv * D``, a pool row's numbers; ``q_rows`` the query
    rows of a slot (``Hq``; an open block folds its width in). At 8 kv heads
    of 128 in bfloat16 that is 4 pages, at 4 kv heads 8."""
    page_bytes = page_size * kv_lanes * itemsize
    # two pools, double-buffered, and as much again where a kv head's rows
    # are joined and V is cast up; the f32 scores, their mask and ``p``
    vmem = 4 * page_bytes + 3 * 4 * q_rows * page_size
    group = 1
    while (group < 8 and 2 * group <= n_pages
           and 4 * group * page_bytes <= _GROUP_BYTES
           and 2 * group * vmem <= _GROUP_VMEM):
        group *= 2
    return group


def decode_work_list(page_table: jnp.ndarray, lengths: jnp.ndarray,
                     page_size: int, sliding_window: int | None = None,
                     group: int = 1) -> DecodeWork:
    """The work list of one decode step (:class:`DecodeWork`) from
    ``page_table`` [B, Pmax] and ``lengths`` [B] (incl. the current token),
    a slot's span (:func:`page_span`) taken ``group`` pages at a time. It is
    the same for every layer: build it once a step, outside the scan over
    layers.

    A slot's last group may run past its span. Such an operand names the
    page the SAME operand held in the item before (the pipeline fetches a
    block only where its index changed, so it moves no bytes), and the
    kernel masks what it holds by position."""
    B, Pmax = page_table.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    first, last = page_span(lengths, page_size, Pmax, sliding_window)
    groups = (last - first) // group + 1                    # [B], >= 1
    ends = jnp.cumsum(groups)
    item = jnp.arange(B * -(-Pmax // group), dtype=jnp.int32)
    # items past n_items are never run; they name the last slot's last group
    row = jnp.minimum(
        jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        B - 1)
    page = first[row] + group * jnp.minimum(
        item - (ends - groups)[row], groups[row] - 1)
    lane = jnp.arange(group, dtype=jnp.int32)[None, :]
    pages = page[:, None] + lane                            # [N, group]
    in_span = pages <= last[row][:, None]
    phys = jnp.asarray(page_table, jnp.int32)[
        row[:, None], jnp.minimum(pages, last[row][:, None])]
    # the newest item at or before this one whose operand held a page (the
    # first item, where none has yet)
    held_at = jax.lax.cummax(jnp.where(in_span, item[:, None], 0), axis=0)
    phys = phys[held_at, lane]
    return DecodeWork(row, page, phys.reshape(-1), lengths, last, ends[-1])


@functools.partial(jax.jit, static_argnames=("interpret", "sliding_window",
                                             "two_d_dots", "scale", "name"))
def paged_decode_attention(
    q: jnp.ndarray,           # [B, Hq, D] — one query token per slot
    k_pool: jnp.ndarray,      # [L, N, page, Hkv*D] — the stacked page pool
    v_pool: jnp.ndarray,
    work: DecodeWork,         # decode_work_list(page_table, lengths, ...)
    layer: jnp.ndarray | int = 0,  # scalar int32 — which layer's pages
    interpret: bool = False,
    sliding_window: int | None = None,
    two_d_dots: bool | None = None,
    scale: float | None = None,
    name: str | None = None,      # the call site's, in a device trace
) -> jnp.ndarray:
    """Returns [B, Hq, D] attention over each slot's paged history in layer
    ``layer`` of the pool. The pool operands reach the ``pallas_call`` as
    they are passed, once for every page of a group (``work.group``): the
    layer and the page are both picked by the blocks' index maps, so the
    pipeline DMAs whole pages and nothing pool-sized is sliced or copied.
    ``sliding_window`` is the one ``work`` was built with; ``scale`` is the
    softmax scale where the model gives one (absent: ``D^-1/2``); ``name`` is
    what a device trace calls this call site's kernel (absent: the kernel's
    own name), for a model that calls it for two kinds of layer.

    ``two_d_dots`` (default: on exactly when compiling for real — Mosaic's
    dot supports only 2D tensors) selects the unrolled per-kv-head 2D-dot
    body, which lane-slices the merged block; interpret mode keeps the
    batched form for tier-1 wall-clock and un-merges the loaded block, which
    Mosaic could not lower and a CPU does for nothing. The two are
    bitwise-identical (golden-pinned)."""
    if two_d_dots is None:
        two_d_dots = not interpret
    B, Hq, D = q.shape
    _, _, page_size, HD = k_pool.shape
    group = work.group

    def page_spec(g: int) -> pl.BlockSpec:
        return pl.BlockSpec(
            (1, 1, page_size, HD),
            lambda i, row, page, phys, ln, last, ly: (
                ly[0], phys[i * group + g], 0, 0))

    q_spec = pl.BlockSpec(
        (1, Hq, D), lambda i, row, page, phys, ln, last, ly: (row[i], 0, 0))
    pages = [page_spec(g) for g in range(group)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(work.n_items,),
        in_specs=[q_spec, *pages, *pages],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, _LANES), jnp.float32),
            pltpu.VMEM((Hq, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size, group=group,
                          sliding_window=sliding_window,
                          two_d_dots=two_d_dots, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret, name=name,
    )(work.row, work.page, work.phys, work.lengths, work.last,
      jnp.asarray(layer, jnp.int32).reshape(1), q,
      *([k_pool] * group), *([v_pool] * group))


def _ragged_kernel(pt_ref, hist_ref, qlen_ref, layer_ref, q_ref, k_ref, v_ref,
                   o_ref,
                   acc_ref, m_ref, l_ref, *, page_size: int, q_block: int,
                   sliding_window: int | None = None,
                   two_d_dots: bool = False,
                   head_dim: int | None = None, block: int = 1,
                   scale: float | None = None):
    """One (slot, q-block, page) program of the ragged mixed-batch kernel.

    Refs:
      pt_ref:   [B, Pmax] int32 SMEM — page table
      hist_ref: [B] int32 SMEM — kv tokens BEFORE this row's query span
      qlen_ref: [B] int32 SMEM — query-span length (0 = idle row)
      layer_ref: [1] int32 SMEM — read by the index maps only
      q_ref:    [1, Qb, Hq, D] VMEM; k_ref/v_ref: [1, 1, page, Hkv*D] VMEM
      o_ref:    [1, Qb, Hq, D] VMEM
      acc_ref:  [Hq*Qb, D] f32; m_ref/l_ref: [Hq*Qb, LANES] f32

    Each query row qi of the block sits at absolute position hist + q0 + qi
    and attends causally over its row's paged KV chain (history AND the
    span's earlier tokens — prefill-chunk self attention). Rows are flat
    r = h*Qb + qi so the GQA dot keeps the decode kernel's head grouping.

    ``two_d_dots`` (the Mosaic-lowerable form): q/o blocks arrive
    MINOR-MERGED too ([1, Qb, Hq*D]; ``head_dim`` un-merges them) and the
    head-major [Qb,Hq,D]↔[Hq,Qb,D] shuffles plus the batched
    GQA dots — the constructs Mosaic cannot lower — become unrolled lane
    slices, sublane/lane concats and per-kv-head 2D dots. Bitwise-identical
    to the batched interpret form (golden-pinned).

    ``block`` (a power of two; 1 = causal): the mask of a model that
    generates by diffusion over blocks — causal between blocks of that many
    absolute positions and full inside one, so the bound of the query at
    ``pos`` is the last position of its block, ``pos | (block - 1)``.
    """
    b = pl.program_id(0)
    qb = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    hist = hist_ref[b]
    qlen = qlen_ref[b]
    q0 = qb * q_block

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = j * page_size
    # last absolute query position this block serves: keys past it are
    # causally invisible to every row of the block, so the page is skipped
    q_hi = hist + jnp.minimum(qlen, q0 + q_block) - 1
    if block > 1:
        q_hi = q_hi | (block - 1)
    relevant = jnp.logical_and(q0 < qlen, k_start <= q_hi)
    if sliding_window is not None:
        # earliest window start across the block's queries
        relevant = jnp.logical_and(
            relevant, k_start + page_size - 1 > hist + q0 - sliding_window)

    @pl.when(relevant)
    def _compute():
        if two_d_dots:
            D = head_dim
            Qb, Hq = q_ref.shape[1], q_ref.shape[2] // D
        else:
            Qb, Hq, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        R = Hq * Qb

        # head-major rows: r = h*Qb + qi (h = kv*G + g), so the GQA grouping
        # matches the decode kernel's reshape(Hkv, G, D) exactly
        if two_d_dots:
            G = Hq // (k_ref.shape[3] // D)
            # the [Qb,Hq,D]→head-major shuffle as unrolled per-head
            # REF-level lane slices of the minor-merged [1, Qb, Hq*D]
            # block feeding per-head 2D dots — neither the rank-3
            # transpose nor a bf16 relayout (both Mosaic-unlowerable) ever
            # appears; only the f32 score tiles concatenate
            scores = _banded_scores_2d(
                [(q_ref[0, :, h * D:(h + 1) * D], h // G)
                 for h in range(Hq)],
                lambda kv: k_ref[0, 0, :, kv * D:(kv + 1) * D],
            )                                    # [R, page], rows h*Qb+qi
        else:
            q = q_ref[0]      # [Qb, Hq, D]
            Hkv = k_ref.shape[3] // D
            k = k_ref[0, 0].reshape(page_size, Hkv, D)
            G = Hq // Hkv
            qt = jnp.transpose(q, (1, 0, 2)).reshape(Hkv, G * Qb, D)
            kt = jnp.transpose(k, (1, 2, 0))    # [Hkv, D, page]
            scores = jax.lax.dot_general(
                qt, kt, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [Hkv, G*Qb, page]
            scores = scores.reshape(R, page_size)
        scores = scores * (1.0 / (D ** 0.5) if scale is None else scale)

        qi = jax.lax.broadcasted_iota(jnp.int32, (R, page_size), 0) % Qb
        q_idx = q0 + qi                          # index within the span
        q_abs = hist + q_idx                     # absolute position
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (R, page_size), 1)
        # causal within the row's own history: k <= this query's position
        # (subsumes k < hist + qlen); padding query rows mask out entirely
        bound = q_abs | (block - 1) if block > 1 else q_abs
        mask = (q_idx < qlen) & (k_pos <= bound)
        if sliding_window is not None:
            mask = mask & (k_pos > q_abs - sliding_window)
        scores = jnp.where(mask, scores, _NEG_INF)

        m_prev = m_ref[...]
        m_blk = jnp.max(scores, axis=1, keepdims=True)      # [R, 1]
        m_new = jnp.maximum(m_prev, jax.lax.broadcast_in_dim(
            m_blk, m_prev.shape, (0, 1)))
        m_ref[...] = m_new
        # a row with no visible key yet still sits at the _NEG_INF floor;
        # the raw exp could poison acc/l for the rest of the walk — such
        # rows carry no mass, so their correction is 0 (this keeps padding
        # query rows inside a partially-valid block at exactly 0.0 in the
        # output, the documented contract, instead of NaN). The floor
        # compare replaces jnp.isfinite: same verdict on every reachable
        # value (masked scores are exactly _NEG_INF, never -inf), and
        # is_finite has no Pallas TPU lowering — the compare is what lets
        # the spec-verify program compile under Mosaic.
        correction = jnp.where(m_new > _NEG_INF * 0.5,
                               jnp.exp(m_prev - m_new), 0.0)  # [R, LANES]
        p = jnp.exp(scores - m_new[:, :1])                  # [R, page]
        p = jnp.where(mask, p, 0.0)
        l_blk = jnp.sum(p, axis=1, keepdims=True)
        l_ref[...] = l_ref[...] * correction + jax.lax.broadcast_in_dim(
            l_blk, m_prev.shape, (0, 1))
        if two_d_dots:
            pv = _banded_weighted_v_2d(
                p, [(h * Qb, Qb, h // G) for h in range(Hq)],
                lambda kv: v_ref[0, 0, :, kv * D:(kv + 1) * D])
        else:
            v = v_ref[0, 0].reshape(page_size, Hkv, D)
            pg = p.reshape(Hkv, G * Qb, page_size)
            vt = jnp.transpose(v, (1, 0, 2))                # [Hkv, page, D]
            pv = jax.lax.dot_general(
                pg, vt.astype(pg.dtype), (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(R, D)
        acc_ref[...] = acc_ref[...] * correction[:, :1] + pv

    @pl.when(j == nj - 1)
    def _finalize():
        Qb = q_ref.shape[1]
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        out = (acc_ref[...] / denom)                        # [Hq*Qb, D]
        if two_d_dots:
            # head-major rows → the minor-merged [Qb, Hq*D] output block
            # via the inverse shuffle: each head's [Qb, D] band
            # concatenates along LANES — a single full-block store, no
            # rank-3 transpose, no strided per-head writes (the wrapper
            # un-merges outside the kernel)
            D = head_dim
            Hq = q_ref.shape[2] // D
            flat = jnp.concatenate(
                [out[h * Qb:(h + 1) * Qb] for h in range(Hq)], axis=1) \
                if Hq > 1 else out                          # [Qb, Hq*D]
            o_ref[0] = flat.astype(o_ref.dtype)
        else:
            Hq, D = q_ref.shape[2], q_ref.shape[3]
            out = out.reshape(Hq, Qb, D)
            o_ref[0] = jnp.transpose(out, (1, 0, 2)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_block", "interpret",
                                             "sliding_window", "two_d_dots",
                                             "block", "scale", "name"))
def ragged_paged_attention(
    q: jnp.ndarray,           # [B, Qmax, Hq, D] — per-row query span, padded
    k_pool: jnp.ndarray,      # [L, N, page, Hkv*D] — the stacked page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Pmax] int32 physical page ids
    hist: jnp.ndarray,        # [B] int32 kv tokens BEFORE the span
    q_lens: jnp.ndarray,      # [B] int32 span length (0 = idle row)
    layer: jnp.ndarray | int = 0,  # scalar int32 — which layer's pages
    q_block: int = 8,
    interpret: bool = False,
    sliding_window: int | None = None,
    two_d_dots: bool | None = None,
    block: int = 1,
    scale: float | None = None,
    name: str | None = None,      # the call site's, in a device trace
) -> jnp.ndarray:
    """Ragged mixed-batch paged attention: one dispatch where each batch row
    attends a variable-length query span over its paged KV chain with causal
    masking relative to its own history. Decode rows (q_len=1) and
    chunked-prefill rows (q_len=chunk) share the batch; idle rows (q_len=0)
    cost one scratch-page read. Returns [B, Qmax, Hq, D]; positions past a
    row's q_len are zeros (their softmax mass is empty).

    The span's own KV must already be present in the pool (the caller
    scatters the chunk's k/v before attending — within-span causality then
    reads the earlier chunk tokens through the page chain). The pool operands
    reach the ``pallas_call`` as they are passed; ``layer`` and the page are
    picked by the blocks' index map.

    ``two_d_dots`` (default: on exactly when compiling for real) replaces
    the head-major [Qb,Hq,D]↔[Hq,Qb,D] shuffles and the batched GQA dots —
    the two constructs Mosaic cannot lower — with unrolled 2D slices/dots;
    bitwise-identical to the batched interpret form (golden-pinned).

    ``block`` > 1 is the block mask (see the kernel): a query sees the keys
    up to the end of its own block of ``block`` absolute positions, which
    must all be in the pool already. ``scale``: the softmax scale where the
    model gives one (absent: ``D^-1/2``). ``name``: what a device trace calls
    this call site's kernel (absent: the kernel's own name), for a model that
    calls it for two kinds of layer."""
    if two_d_dots is None:
        two_d_dots = not interpret
    if block & (block - 1):
        raise ValueError(f"block {block} must be a power of two")
    B, Qmax, Hq, D = q.shape
    _, _, page_size, HD = k_pool.shape
    Pmax = page_table.shape[1]
    if Qmax % q_block:
        raise ValueError(f"Qmax {Qmax} must be a multiple of q_block {q_block}")

    def _page_index(b, qb, j, pt_ref, hist_ref, qlen_ref, layer_ref):
        # clamp j into the pages this (row, q-block) can actually see so
        # skipped programs revisit the resident page and the DMA is elided
        hist_b = hist_ref[b]
        qlen = qlen_ref[b]
        q_hi = hist_b + jnp.minimum(qlen, (qb + 1) * q_block) - 1
        if block > 1:
            q_hi = q_hi | (block - 1)
        last = jnp.maximum(q_hi // page_size, 0)
        jj = jnp.minimum(j, last)
        if sliding_window is not None:
            lo = jnp.maximum(
                (hist_b + qb * q_block - sliding_window) // page_size, 0)
            jj = jnp.maximum(jj, jnp.minimum(lo, last))
        return (layer_ref[0], pt_ref[b, jj], 0, 0)

    kv_spec = pl.BlockSpec((1, 1, page_size, HD), _page_index)
    if two_d_dots:
        # q/o travel MINOR-MERGED like the pool (a request-sized reshape):
        # in-kernel merges of loaded blocks are unsupported vector
        # shape_casts under Mosaic, lane slices of 2D blocks are not
        q_in = q.reshape(B, Qmax, Hq * D)
        q_spec = pl.BlockSpec((1, q_block, Hq * D),
                              lambda b, qb, j, pt, hh, ql, ly: (b, qb, 0))
    else:
        q_in = q
        q_spec = pl.BlockSpec((1, q_block, Hq, D),
                              lambda b, qb, j, pt, hh, ql, ly: (b, qb, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Qmax // q_block, Pmax),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hq * q_block, D), jnp.float32),
            pltpu.VMEM((Hq * q_block, _LANES), jnp.float32),
            pltpu.VMEM((Hq * q_block, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size,
                          q_block=q_block, sliding_window=sliding_window,
                          two_d_dots=two_d_dots,
                          head_dim=D if two_d_dots else None, block=block,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), hist.astype(jnp.int32),
      q_lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q_in, k_pool, v_pool)
    return out.reshape(B, Qmax, Hq, D)


def paged_block_attention(q, k_pool, v_pool, work: DecodeWork, layer=0,
                          interpret: bool = False):
    """The open block of a model that generates by diffusion over blocks:
    ``q`` [B, W, Hq, D], the block's W queries a row, every one of which sees
    all of ``work.lengths`` keys (the row's kept history and the block
    itself, which the caller has written). That is
    :func:`paged_decode_attention` with the block folded into the GQA group
    axis, W x G query rows a kv head, so the pages are walked once a row and
    not once a position. No sliding window: ``work`` is built without one.
    Returns [B, W, Hq, D]."""
    B, W, Hq, D = q.shape
    Hkv = k_pool.shape[3] // D
    G = Hq // Hkv
    folded = q.reshape(B, W, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    out = paged_decode_attention(
        folded.reshape(B, Hkv * W * G, D), k_pool, v_pool, work, layer,
        interpret=interpret)
    return out.reshape(B, Hkv, W, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, W, Hq, D)


def paged_gather_dense(k_pool, v_pool, page_table, head_dim, layer=0):
    """Reference helper: materialize each slot's paged KV in one layer of
    the merged pool as a dense cache [B, Pmax*page, Hkv, D] (tests and
    chip_smoke.py only — O(pool) reads)."""
    k = jnp.take(k_pool[layer], page_table, axis=0)  # [B, Pmax, page, Hkv*D]
    v = jnp.take(v_pool[layer], page_table, axis=0)
    B, Pmax, page, HD = k.shape
    return (k.reshape(B, Pmax * page, HD // head_dim, head_dim),
            v.reshape(B, Pmax * page, HD // head_dim, head_dim))
