"""Pallas paged-attention kernels over a paged KV pool: decode (T=1), and
the ragged mixed-batch kernel of a prompt's chunks.

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

The ragged kernel (``ragged_paged_attention``: the lanes of a mixed step)
has no grid over the table at all since PR 55 (ROADMAP S18, closed): a
program is a (lane, block of 64 queries) and walks the pages its queries see
itself, through ``ops/page_walk.py``'s ``_Walk`` (the latent kernels' walk,
here over K and V pages that share a page id, a ring each): the spans are
worked out once a call and ride in as scalar prefetch, a trip of up to 16
pages is ONE key block with one score dot, one online-softmax update and one
value dot a kv head over its ``G x 64`` query rows, and no program and no
copy exists for a slot of the table outside a span.

Why this beats the dense path (VERDICT r1 weak #3/#6): attention reads scale
with the *tokens actually present* (sum of per-slot lengths), not
n_slots × max_seq — idle slots cost one scratch-page read, and short sequences
don't drag the whole window through HBM every step. Pages are shared
cross-request (prefix cache) with zero copies: sharing is rows in the page
table, exactly the PAPERS.md "ragged paged attention for TPU" direction.

Both kernels take the STACKED pool as the pool keeps it — ``[L, N, page,
Hkv*D]``, head-major on the merged minor axis (runtime/paged.py) — and the
layer as one more scalar-prefetch operand: a block (the decode kernel's) or
a DMA (the ragged kernel's) is a page ``(page, Hkv*D)`` at ``(layer,
page_table[b, jj])``, so nothing pool-sized is sliced, reshaped or copied
in front of the call. (On a tiled TPU layout a merge of
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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import page_walk
from .page_walk import (_LANES, _NEG_INF, _VMEM_LIMIT, _Walk,
                        _online_softmax_step, _span_first, _walk_scratch,
                        page_span, ragged_span)


def _banded_weighted_v_2d(p, row_bands, v_of):
    """The decode kernel's p@v as unrolled 2D dots — no rank-3 transpose, no
    batched dot_general (Mosaic's dot supports only 2D operands): per-band
    [rows, page] x [page, D] dots against ``v_of(kv)``, a kv head's [page,
    D] value slice — a REF-level lane slice of the minor-merged [1, 1, page,
    Hkv*D] block (the pool is stored merged): value-level bf16 lane slices
    at non-zero tile offsets are an unlowerable relayout, ref-level sliced
    LOADS are not. The per-band results concatenate in f32 (bf16 sublane
    concats are an unsupported multi-row shift) back to head-major rows;
    each output element is the same contraction the batched dot computes.
    ``row_bands`` lists (row_start, rows, kv_head); ``p`` is f32, so its
    sublane band slices lower (32-bit shifts are implemented, 16-bit are
    not)."""
    outs = [jax.lax.dot_general(
        p[s:s + n], v_of(kv).astype(p.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for s, n, kv in row_bands]
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


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
        each, taken at the ref: see :func:`_banded_weighted_v_2d`; a page of 16
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


#: accumulator bytes a q-block may hold (acc, m and l: three rows of 128
#: float32 a query row): 64 queries of 85 heads. The rings, the q and o
#: blocks and one trip's scores of a kv head's rows take up to twice as much
#: again, of ``_VMEM_LIMIT``
_Q_BLOCK_BYTES = 8 << 20


def ragged_q_block(width: int, heads: int) -> int:
    """Queries a program of the ragged kernel takes, from shapes: 64 (the
    rows one kv head's score dot has are this times its group: 64 is 10-18%
    under 32 alone at a chunk of 512, 128 another 3-7% for twice the
    accumulators: PERF.md, PR 55), the lane's ``width`` where that is less,
    and half as many for as long as ``heads`` rows a query of float32
    accumulator, running max and running sum pass ``_Q_BLOCK_BYTES``. A
    width that is not whole q-blocks is padded by the wrapper."""
    q_block = min(64, width)
    while q_block > 8 and heads * q_block * 3 * _LANES * 4 > _Q_BLOCK_BYTES:
        q_block //= 2
    return q_block


#: keys a trip of the ragged kernel takes at most. A trip's fixed cost is
#: the flash state's arithmetic, 15 vector operations a row of the
#: accumulator, the running max and the running sum a kv head: about what
#: 256 keys of scores cost, and 5 us a trip at laguna's 72 heads. 1 024 keys
#: (16 pages of 64) is 2.0-2.6 times under 256 alone behind 2-4 k of history
#: and even with 2 048; a kv head's G x Qb rows are 64-1 024 a score dot
#: where the latent kernel's are 2 048 (PERF.md, PR 55, has the probe)
RAGGED_TRIP_KEYS = 1024


def ragged_trip_pages(page_size: int, sliding_window: int | None,
                      q_block: int) -> int:
    """Pages a trip of the ragged kernel takes (``page_walk``'s rule at
    ``RAGGED_TRIP_KEYS``): 16 of 64 tokens, and no more than a q-block's
    windows span (laguna's window of 512: 10), so that a window layer's
    program is one trip."""
    return page_walk.ragged_trip_pages(page_size, sliding_window, q_block,
                                       RAGGED_TRIP_KEYS)


def ragged_block_sizes(trip: int) -> tuple[int, ...]:
    """The key blocks a trip of the ragged kernel is attended over as, in
    pages: the trip, and 4 pages (256 keys) for what a short lane, a
    q-block with little behind it or a span's last trip holds. Two and not
    the latent kernels' four (2 / 4 / 8 / 16): each size is two traced bodies
    of the attend in every program the kernel sits in, and with four the
    16-row cells paid for them at every start (qwen2 ``jax_trace_lower_s``
    11.7 -> 13.4 s on a warm traced pair; PERF.md, PR 55)."""
    return tuple(sorted({min(4, trip), trip}))


def ragged_walk(hist, q_lens, width: int, page_size: int, n_pages: int,
                sliding_window: int | None, heads: int, block: int = 1,
                trip: int | None = None) -> tuple[int, int]:
    """(pages, trips) a call of :func:`ragged_paged_attention` over lanes of
    ``width`` queries of ``heads`` query heads walks: the copies it starts
    in EACH pool and the key blocks it attends over, by the kernel's own
    spans, on the host (NumPy)."""
    q_block = ragged_q_block(width, heads)
    return page_walk.ragged_walk(
        hist, q_lens, width, page_size, n_pages, sliding_window, q_block,
        trip or ragged_trip_pages(page_size, sliding_window, q_block), block)


def _ragged_kernel(pt_ref, first_ref, last_ref, hist_ref, qlen_ref, layer_ref,
                   q_ref, k_pool_ref, v_pool_ref, o_ref, k_ring, v_ring, sem,
                   walk_ref, acc_ref, m_ref, l_ref, *, page_size: int,
                   q_block: int, q_blocks: int, trip: int,
                   sliding_window: int | None, two_d_dots: bool, block: int,
                   scale: float | None):
    """One (lane, q-block) of the ragged mixed-batch kernel: the program
    walks the pages its queries see itself (``ops/page_walk.py``:
    :class:`_Walk`, an item a (lane, q-block), lanes in order; K and V of a
    page are copied together, each into its own ring).

    Refs:
      pt_ref:   [R, Pmax] int32 SMEM — the lanes' rows of the page table
      first_ref, last_ref: [R * q_blocks] int32 SMEM — an item's span
        (:func:`ragged_span`; one that reads nothing has ``last < first``)
      hist_ref: [R] int32 SMEM — kv tokens BEFORE this lane's query span
      qlen_ref: [R] int32 SMEM — query-span length (0 = idle lane)
      layer_ref: [1] int32 SMEM
      q_ref:    [1, 1, Hkv, G*Qb, D] VMEM — a kv head's ``G x Qb`` query rows
        one slab, row ``g * Qb + qi`` (the wrapper's layout)
      k_pool_ref, v_pool_ref: the whole stacked pools, where they live
      o_ref:    [1, 1, Hkv, G*Qb, D] VMEM
      k_ring, v_ring, sem, walk_ref, acc_ref, m_ref, l_ref:
        :func:`_walk_scratch` of two pools at ``Hq * Qb`` rows

    Query ``qi`` of the block sits at absolute position hist + q0 + qi and
    attends causally over its lane's paged KV chain (history AND the span's
    earlier tokens: prefill-chunk self attention), the last
    ``sliding_window`` keys of it. Accumulator rows are flat ``r = h*Qb +
    qi`` (h = kv*G + g), the decode kernel's head grouping.

    A trip's pages are ONE key block: one score dot, one online-softmax
    update and one value dot a kv head, over its slab of the accumulators,
    under one mask by query position. ``two_d_dots`` (the Mosaic-lowerable
    form) takes a head's keys and values as REF-level lane slices of the
    minor-merged ring block (:func:`_banded_weighted_v_2d` says why) and runs
    the heads one after another; the batched form is one dot over every kv
    head, which Mosaic cannot lower and interpret mode keeps for tier-1
    wall-clock. Bitwise identical.

    ``block`` (a power of two; 1 = causal): the mask of a model that
    generates by diffusion over blocks — causal between blocks of that many
    absolute positions and full inside one, so the bound of the query at
    ``pos`` is the last position of its block, ``pos | (block - 1)``.
    """
    b, qb = pl.program_id(0), pl.program_id(1)
    Hkv, GQ, D = q_ref.shape[2:]
    item = b * q_blocks + qb
    walk = _Walk(pt_ref, layer_ref, (k_pool_ref, v_pool_ref),
                 (k_ring, v_ring), sem, walk_ref,
                 n_items=first_ref.shape[0], trip=trip,
                 sizes=ragged_block_sizes(trip), page_size=page_size,
                 idle=lambda item: last_ref[item] < first_ref[item],
                 span=lambda item: (first_ref[item], last_ref[item]),
                 row=lambda item: lax.div(item, q_blocks), unroll=False)

    @pl.when(item == 0)
    def _open():
        walk.open()

    hist, qlen = hist_ref[b], qlen_ref[b]
    q0 = qb * q_block

    @pl.when(q0 >= qlen)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def visible(k_pos):
        """The keys each row of a block of key positions [rows, keys] sees
        (rows ``g * Qb + qi``, of one kv head or of all): causal within the
        lane's own history (which subsumes k < hist + qlen), inside the
        window; a padding query row sees nothing."""
        q_idx = q0 + jax.lax.broadcasted_iota(
            jnp.int32, k_pos.shape, 0) % q_block
        q_abs = hist + q_idx
        bound = q_abs | (block - 1) if block > 1 else q_abs
        mask = (q_idx < qlen) & (k_pos <= bound)
        if sliding_window is not None:
            mask = mask & (k_pos > q_abs - sliding_window)
        return mask

    def attend(slot, k_start, *, pages, first):
        keys = pl.ds(0, pages * page_size)
        n_keys = pages * page_size
        sm_scale = 1.0 / (D ** 0.5) if scale is None else scale
        rows = GQ if two_d_dots else Hkv * GQ
        mask = visible(k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, n_keys), 1))
        if not two_d_dots:
            k = k_ring[slot, keys].reshape(n_keys, Hkv, D)
            scores = jax.lax.dot_general(
                q_ref[0, 0], jnp.transpose(k, (1, 2, 0)),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # [Hkv, GQ, keys]

            def weigh(p):
                v = v_ring[slot, keys].reshape(n_keys, Hkv, D)
                pg = p.reshape(Hkv, GQ, n_keys)
                return jax.lax.dot_general(
                    pg, jnp.transpose(v, (1, 0, 2)).astype(pg.dtype),
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32).reshape(rows, D)

            _online_softmax_step(scores.reshape(rows, n_keys) * sm_scale,
                                 mask, weigh, acc_ref, m_ref, l_ref, first)
            return

        def one_head(kv, _):
            if isinstance(kv, int):
                head, slab = pl.ds(kv * D, D), pl.ds(kv * GQ, GQ)
            else:
                head = pl.ds(pl.multiple_of(lax.mul(kv, D), _LANES), D)
                slab = pl.ds(pl.multiple_of(lax.mul(kv, GQ), 8), GQ)
            scores = jax.lax.dot_general(
                q_ref[0, 0, kv], k_ring[slot, keys, head],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [GQ, keys]
            _online_softmax_step(
                scores * sm_scale, mask,
                lambda p: jax.lax.dot_general(
                    p, v_ring[slot, keys, head].astype(p.dtype),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32),
                acc_ref.at[slab], m_ref.at[slab], l_ref.at[slab], first)

        if D % _LANES:
            # a head's lanes at an offset that is no whole lane tile: only a
            # static slice lowers (phi-3's head of 96)
            for kv in range(Hkv):
                one_head(kv, None)
        else:
            # a loop: the body is a kv head's G x Qb rows of dots, long
            # enough not to feel it, and traced and lowered once a kernel
            # where unrolled heads cost 8-16 bodies in every program the
            # kernel sits in (set-up: PERF.md, PRs 53 and 55)
            lax.fori_loop(0, Hkv, one_head, None)

    @pl.when(q0 < qlen)
    def _busy():
        walk.run(item, attend)
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).reshape(
            Hkv, GQ, D).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "interpret", "sliding_window", "two_d_dots", "block", "scale", "name",
    "trip", "q_block"))
def ragged_paged_attention(
    q: jnp.ndarray,           # [R, Qc, Hq, D] — per-lane query span, padded
    k_pool: jnp.ndarray,      # [L, N, page, Hkv*D] — the stacked page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # [R, Pmax] int32 physical page ids
    hist: jnp.ndarray,        # [R] int32 kv tokens BEFORE the span
    q_lens: jnp.ndarray,      # [R] int32 span length (0 = idle lane)
    layer: jnp.ndarray | int = 0,  # scalar int32 — which layer's pages
    *,
    interpret: bool | pltpu.InterpretParams = False,
    sliding_window: int | None = None,
    two_d_dots: bool | None = None,
    block: int = 1,
    scale: float | None = None,
    name: str | None = None,      # the call site's, in a device trace
    trip: int | None = None,      # a test's or a probe's pages a trip
    q_block: int | None = None,   # ... and its queries a program
) -> jnp.ndarray:
    """Ragged mixed-batch paged attention: one dispatch where each lane
    attends a variable-length query span over its paged KV chain with causal
    masking relative to its own history. Decode rows (q_len=1) and
    chunked-prefill lanes (q_len=chunk) share the batch; an idle lane
    (q_len=0) costs nothing. Returns [R, Qc, Hq, D]; positions past a lane's
    q_len are zeros (their softmax mass is empty).

    The span's own KV must already be present in the pool (the caller
    scatters the chunk's k/v before attending — within-span causality then
    reads the earlier chunk tokens through the page chain).

    One program a (lane, block of :func:`ragged_q_block` queries), in
    order; the pools stay where they live and the programs copy the pages of
    a q-block's span themselves, K and V of a page together,
    :func:`ragged_trip_pages` at a time (16 of 64 tokens: a key block of
    1 024 keys a score dot). The spans (:func:`ragged_span`: under the window
    and the block mask) are worked out here, once a call, and ride in as
    scalar-prefetch operands; no program and no copy exists for a slot of
    the table outside a span. ``q`` goes in with a kv head's ``G x q_block``
    query rows one slab a q-block and the output comes back the same way
    (request-sized transposes, here), a width that is not whole q-blocks
    padded. At ``trip=1`` and ``q_block=8`` the sums run in the order of the
    grid this kernel had up to PR 54 (a program a slot of the table, a page
    a program) and give its bytes; at the shipped trip it is another order
    of the same sums.

    ``two_d_dots`` (default: on exactly when compiling for real) runs the
    kv heads' 2D dots one after another over ref-level lane slices, where
    the batched form is one dot over every kv head, which Mosaic cannot
    lower; bitwise-identical (golden-pinned).

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
    R, Qc, Hq, D = q.shape
    _, _, page_size, HD = k_pool.shape
    Hkv = HD // D
    G = Hq // Hkv
    if Qc % 8:
        raise ValueError(f"a lane of {Qc} queries is not a multiple of 8 "
                         "(whole sublane tiles)")
    q_block = q_block or ragged_q_block(Qc, Hq)
    q_blocks = -(-Qc // q_block)
    width = q_blocks * q_block
    trip = trip or ragged_trip_pages(page_size, sliding_window, q_block)
    hist, q_lens = hist.astype(jnp.int32), q_lens.astype(jnp.int32)
    first, last = ragged_span(hist, q_lens, width, page_size,
                              page_table.shape[1], sliding_window, q_block,
                              block)
    if width != Qc:
        q = jnp.pad(q, ((0, 0), (0, width - Qc), (0, 0), (0, 0)))
    # [R, Qc, (Hkv, G), D] -> [R, q-block, Hkv, (G, Qb), D]
    slabs = (R, q_blocks, Hkv, G * q_block, D)
    q = q.reshape(R, q_blocks, q_block, Hkv, G, D).transpose(
        0, 1, 3, 4, 2, 5).reshape(slabs)

    def at_block(b, qb, *_):
        return (b, qb, 0, 0, 0)

    block_spec = pl.BlockSpec((1, 1, *slabs[2:]), at_block)
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size,
                          q_block=q_block, q_blocks=q_blocks, trip=trip,
                          sliding_window=sliding_window,
                          two_d_dots=two_d_dots, block=block, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(R, q_blocks),
            in_specs=[block_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block_spec,
            scratch_shapes=_walk_scratch(trip, page_size, HD, k_pool.dtype,
                                         Hq * q_block, D, pools=2)),
        out_shape=jax.ShapeDtypeStruct(slabs, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), first.reshape(-1), last.reshape(-1),
      hist, q_lens, jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool,
      v_pool)
    out = out.reshape(R, q_blocks, Hkv, G, q_block, D).transpose(
        0, 1, 4, 2, 3, 5).reshape(R, width, Hq, D)
    return out[:, :Qc] if width != Qc else out


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
