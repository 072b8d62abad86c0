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
``ops/paged_attention.py``'s. The queries arrive HEAD-MAJOR and already
absorbed, and the softmax scale is the caller's (it carries YaRN's
``mscale^2``).

**Both kernels walk their pages inside the program** (:class:`_Walk`; the
decode kernel since PR 49, a grid step a group of pages before; the ragged
kernel since PR 53, a grid step a page before). The pool is left where it
lives and the page table, the lengths and the layer are scalar-prefetch
operands. A call's work is a sequence of ITEMS, one a program, in order: the
decode kernel's rows (``grid=(B,)``), the ragged kernel's (lane, block of
:func:`ragged_q_block` queries) pairs (``grid=(R, Qc // q_block)``). A
**trip** takes consecutive pages of an item's span (``page_span`` /
:func:`ragged_span`: what the scheduler's walked counters count), one DMA a
page and only for the pages inside the span, into one key block of a ring of
``RING_BLOCKS``, where they land as the rows of one ``[trip * page, lanes]``
block: one score dot, one mask by position, one online-softmax update, one
value dot, over the smallest block that holds what the trip copied
(:func:`_block_sizes`). The trips of a call are one sequence (items in
order, an item that holds nothing has none) and the ring runs through it
across the programs: the copies of the trips after the one being attended
over are in flight, so an item's last trips start the next item's first.

The kernels differ in how many positions a program's queries have. A decode
row is one query at ``length - 1`` and takes :func:`trip_pages` pages a trip
(16; ``llm_attn_page_groups_total`` counts its trips). A q-block is ``q_block``
queries, head-major rows ``h * q_block + qi`` at ``hist + q0 + qi``, masked
by query (causal, and the window's other edge); its trip is
:func:`ragged_trip_pages` pages (4 of 64 tokens: a key block of 256 keys, so
both dots run at full 128-wide MXU tiles and the accumulator, 4-5 MB at
64-80 heads, is rescaled once a trip; ``llm_ragged_trips_total`` counts
them).

**A window** (``sliding_window``, static; ``models/motif.py``'s window
layers): a query at ``t`` sees the keys ``t - window < s <= t``. Both kernels
start an item at the first page of that span (``paged_attention._span_first``)
and mask the rest, so what an item costs does not grow with its length (one
trip of the pages a window, or a q-block's windows, span: no second pass over
the accumulator), and the pages left of the span are never read: the pool may
have given them to another row. Without a window the decode kernel gives
what it gave before PR 48, bit for bit, and the ragged kernel at ``trip=1``
what it gave before PR 53 (at its own trip: another order of the same sums).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _LANES, _NEG_INF, _span_first, page_span

_VMEM_LIMIT = 64 * 1024 * 1024


def _online_softmax_step(scores, mask, value, acc_ref, m_ref, l_ref,
                         first: bool = False):
    """One block of keys of the flash recurrence: ``scores`` [R, keys] f32
    (already scaled), ``mask`` its visible keys, ``value`` [keys, rank] the
    first ``rank`` lanes of the same latent rows. ``first`` (static): the
    accumulators hold nothing yet and are not read; what is written is what
    the recurrence gives from its start (m at the floor, l and acc zero),
    bit for bit."""
    scores = jnp.where(mask, scores, _NEG_INF)
    m_blk = jnp.max(scores, axis=1, keepdims=True)
    m_prev = jnp.full(m_ref.shape, _NEG_INF, m_ref.dtype) if first \
        else m_ref[...]
    m_new = jnp.maximum(m_prev, jax.lax.broadcast_in_dim(
        m_blk, m_prev.shape, (0, 1)))
    m_ref[...] = m_new
    p = jnp.where(mask, jnp.exp(scores - m_new[:, :1]), 0.0)
    l_blk = jax.lax.broadcast_in_dim(
        jnp.sum(p, axis=1, keepdims=True), m_prev.shape, (0, 1))
    pv = jax.lax.dot_general(p.astype(value.dtype), value,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if first:
        l_ref[...] = l_blk
        acc_ref[...] = pv
        return
    # a row with no visible key yet sits at the floor: it carries no mass
    correction = jnp.where(m_new > _NEG_INF * 0.5,
                           jnp.exp(m_prev - m_new), 0.0)
    l_ref[...] = l_ref[...] * correction + l_blk
    acc_ref[...] = acc_ref[...] * correction[:, :1] + pv


#: latent pages a trip of the decode kernel takes at most: the key block of
#: one score dot and one update of the accumulator. A trip's fixed cost (the
#: accumulator's rescale, the waits) is about what 4 pages' dots cost, and
#: what a row's last trip holds under 16 is attended over as a smaller block
#: (:func:`_block_sizes`): PERF.md, PR 49, has the probe that read 8 / 16 /
#: 32 at five shapes (16 is 11-15% under 8 and 2-5% under 32)
TRIP_PAGES = 16


def _window_pages(sliding_window: int, page_size: int,
                  queries: int = 1) -> int:
    """The most pages the window of ``queries`` consecutive positions spans
    (``ModelConfig.window_pages``, for a kernel that has no configuration)."""
    return (sliding_window + queries - 3) // page_size + 2


def trip_pages(page_size: int, sliding_window: int | None) -> int:
    """Pages a trip of the decode kernel takes, from shapes: ``TRIP_PAGES``,
    and no more than a window spans."""
    if sliding_window is None:
        return TRIP_PAGES
    return min(TRIP_PAGES, _window_pages(sliding_window, page_size))


#: key blocks of the decode kernel's ring: the one a trip attends over and
#: the trips whose copies are in flight behind it. With one in flight a copy
#: has a trip's body to arrive in, and takes about that long itself, so every
#: trip waited out the DMA's latency (PERF.md, PR 49: the probe at 2 and 3)
RING_BLOCKS = 3


def _block_sizes(trip: int) -> tuple[int, ...]:
    """The key blocks a trip is attended over as, in pages: the powers of
    two from 2 (128 keys: a lane tile of scores) below ``trip``, and
    ``trip``. A trip takes the smallest that holds its pages, so a row's last
    trip, a window's two pages and a row that holds one token do not pay for
    the dots of a whole block."""
    sizes = []
    size = 2
    while size < trip:
        sizes.append(size)
        size *= 2
    return (*sizes, trip)


def _attend_trip(q, ring_ref, slot, k_start, visible, acc_ref, m_ref, l_ref,
                 *, pages: int, page_size: int, rank: int, scale: float,
                 first: bool):
    """One trip of a walk: the first ``pages`` pages of key block ``slot``
    are the rows of ONE key/value block whose first key sits at ``k_start``,
    attended over by the query rows ``q`` [rows, lanes]. A page is read once
    and is the key (all its lanes) and the value (its first ``rank``).
    ``visible``: the mask of a block of key positions [rows, keys], the one
    thing the two kernels differ in (a decode row is one position, a q-block
    one a query). ``first``: the first trip of its walk."""
    keys = pl.ds(0, pages * page_size)
    scores = jax.lax.dot_general(
        q, ring_ref[slot, keys], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale         # [rows, keys]
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    _online_softmax_step(scores, visible(k_pos),
                         ring_ref[slot, keys, pl.ds(0, rank)],
                         acc_ref, m_ref, l_ref, first)


@dataclasses.dataclass(frozen=True)
class _Walk:
    """The walk both kernels make. A call's work is a sequence of ITEMS (the
    decode kernel's rows; the ragged kernel's (lane, q-block) pairs), one a
    program, and the programs run in order. An item reads the logical pages
    ``span(item)`` of row ``row(item)`` of the page table, ``trip`` pages a
    trip, one DMA a page and only for the pages inside the span; an item
    that is ``idle`` has no trips. The call's trips are ONE sequence (items
    in order, an item's trips ascending) and the ring of key blocks runs
    through it across the programs: while a trip is attended over, the
    ``blocks - 1`` after it are in flight. walk_ref [3] SMEM carries (the key
    block of the next trip to attend over, the item and the number of the
    next trip to START) from a program to the next.

    Every copy started has exactly one wait: the trip ``(item, j)`` is
    waited for by program ``item`` at its ``j``-th trip, under the condition
    it was started under (the page lies in the span)."""
    pt_ref: Any         # [rows, Pmax] SMEM: the page table
    layer_ref: Any      # [1] SMEM
    pool_ref: Any       # the whole stacked pool, where it lives
    ring_ref: Any       # [blocks, trip * page, lanes] VMEM: the key blocks
    sem: Any            # a DMA semaphore a key block
    walk_ref: Any       # [3] SMEM
    n_items: int
    trip: int
    page_size: int
    idle: Callable      # item -> it holds nothing
    span: Callable      # item -> (first, last) logical page it reads
    row: Callable       # item -> its row of the page table
    #: the copies of a trip written out under a condition a page and an
    #: item's first trip peeled off the loop (the decode kernel: the form PR
    #: 49 measured; as loops its 16 copies a trip read 7.5-13.6% slower
    #: alone), or both as loops (the ragged kernel: a trip's body is 2 000
    #: rows of dots and does not feel them, and a kernel is traced and
    #: lowered in every program it sits in, six widths of ``mixed_step`` a
    #: server: half the seconds an instance). PERF.md, PR 53.
    unroll: bool

    @property
    def blocks(self) -> int:
        return self.ring_ref.shape[0]

    # The bookkeeping below is scalar arithmetic written in ``lax``
    # primitives, not operators: under a kernel's trace every ``jnp``
    # operator is a nested jit of its own, 0.3-0.4 ms of Python each, and a
    # kernel is traced and lowered again in every program it sits in (six
    # widths of ``mixed_step`` a server; PERF.md, PR 53).

    def _trips(self, item):
        """(first, last, trips) of ``item``'s span; ``last = first - 1``
        (the ragged kernel's idle item) is no trip."""
        start, last = self.span(item)
        return start, last, lax.div(
            lax.add(lax.sub(last, start), self.trip), self.trip)

    def _page(self, start, j):
        """The first logical page of trip ``j``."""
        return lax.add(start, lax.mul(j, self.trip))

    def _held(self, start, last, j):
        """Pages trip ``j`` of the span ``start .. last`` holds."""
        return lax.min(lax.add(lax.sub(last, self._page(start, j)), 1),
                       self.trip)

    def _copies(self, item, start, last, j, slot, do):
        """``do`` (start or wait) the copy of every page of trip ``j`` of
        ``item`` that lies in its span, into key block ``slot``: a spare
        page moves no bytes."""
        row, first = self.row(item), self._page(start, j)

        def copy(t):
            at = t * self.page_size if self.unroll else pl.multiple_of(
                lax.mul(t, self.page_size), self.page_size)
            do(pltpu.make_async_copy(
                self.pool_ref.at[self.layer_ref[0],
                                 self.pt_ref[row, lax.add(first, t)]],
                self.ring_ref.at[slot, pl.ds(at, self.page_size)],
                self.sem.at[slot]))

        if self.unroll:
            for t in range(self.trip):
                pl.when(lax.le(lax.add(first, t), last))(
                    functools.partial(copy, t))
        else:
            lax.fori_loop(0, self._held(start, last, j),
                          lambda t, _: copy(t), None)

    def _next_busy(self, item):
        """The first item at or after ``item`` that holds something;
        ``n_items`` where none does."""
        n = self.n_items
        return lax.while_loop(
            lambda i: lax.lt(i, n) & self.idle(lax.min(i, n - 1)),
            lambda i: lax.add(i, 1), lax.min(item, n))

    def _start_next(self, slot, item, j):
        """Start the trip ``(item, j)`` (none: ``item`` is ``n_items``) into
        key block ``slot``; returns the trip after it."""
        at = lax.min(item, self.n_items - 1)
        start, last, trips = self._trips(at)

        @pl.when(lax.lt(item, self.n_items))
        def _():
            self._copies(at, start, last, j, slot, lambda c: c.start())

        after = lax.add(j, 1)
        return lax.cond(
            lax.lt(after, trips), lambda: (item, after),
            lambda: (self._next_busy(lax.add(item, 1)), lax.mul(j, 0)))

    def _wrap(self, slot):
        return lax.select(lax.ge(slot, self.blocks),
                          lax.sub(slot, self.blocks), slot)

    def open(self):
        """The call's first program: the first ``blocks - 1`` trips go out."""
        # a row of a key block no trip has written yet must hold numbers: a
        # zero probability times a NaN is a NaN in the value dot. After this
        # a block holds zeros or pages of some span, which the mask drops
        self.ring_ref[...] = jnp.zeros_like(self.ring_ref)
        nxt = (self._next_busy(jnp.int32(0)), jnp.int32(0))
        if self.unroll:
            for slot in range(self.blocks - 1):
                nxt = self._start_next(slot, *nxt)
        else:
            nxt = lax.fori_loop(
                0, self.blocks - 1,
                lambda slot, nxt: self._start_next(slot, *nxt), nxt)
        self.walk_ref[0] = 0
        self.walk_ref[1], self.walk_ref[2] = nxt

    def run(self, item, attend):
        """Program ``item``'s trips, in order: ``attend(slot, k_start,
        pages=, first=)`` over each, as the smallest block that holds what
        the trip copied (:func:`_block_sizes`). ``first``: an item's first
        trip finds nothing in the accumulators and reads nothing from them;
        where an item is one trip (a window's) that is the whole of it."""
        walk_ref = self.walk_ref
        start, last, trips = self._trips(item)
        sizes = _block_sizes(self.trip)

        def one_trip(j, walk, first=None):
            """``first``: whether ``j`` is the first trip, where that is
            known as the trip is traced (peeled); None: a condition on
            ``j``."""
            slot, *nxt = walk
            # the copies of the trip ``blocks - 1`` on go out before this
            # one's are waited for
            nxt = self._start_next(
                self._wrap(lax.add(slot, self.blocks - 1)), *nxt)
            self._copies(item, start, last, j, slot, lambda c: c.wait())
            held = self._held(start, last, j)
            k_start = lax.mul(self._page(start, j), self.page_size)
            for under, pages in zip((0, *sizes), sizes):
                fits = lax.gt(held, under) & lax.le(held, pages)
                for is_first in (True, False) if first is None else (first,):
                    when = fits if first is not None else fits & (
                        lax.eq if is_first else lax.gt)(j, 0)
                    pl.when(when)(functools.partial(
                        attend, slot, k_start, pages=pages, first=is_first))
            return (self._wrap(lax.add(slot, 1)), *nxt)

        walk = (walk_ref[0], walk_ref[1], walk_ref[2])
        if self.unroll:
            walk = one_trip(0, walk, first=True)
            walk = lax.fori_loop(
                1, trips, functools.partial(one_trip, first=False), walk)
        else:
            walk = lax.fori_loop(0, trips, one_trip, walk)
        walk_ref[0], walk_ref[1], walk_ref[2] = walk


def _walk_scratch(trip: int, page_size: int, width: int, dtype, rows: int,
                  rank: int) -> list:
    """What a walking kernel keeps between its programs: the ring, its
    semaphores, the cursor, and the flash accumulators of ``rows`` query
    rows (acc [rows, rank], m / l [rows, LANES], float32)."""
    return [pltpu.VMEM((RING_BLOCKS, trip * page_size, width), dtype),
            pltpu.SemaphoreType.DMA((RING_BLOCKS,)),
            pltpu.SMEM((3,), jnp.int32),
            pltpu.VMEM((rows, rank), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32)]


def _decode_kernel(pt_ref, len_ref, layer_ref, q_ref, pool_ref, o_ref,
                   ring_ref, sem, walk_ref, acc_ref, m_ref, l_ref, *,
                   page_size: int, trip: int, rank: int, scale: float,
                   sliding_window: int | None):
    """One slot: the program walks its row's span itself (:class:`_Walk`, an
    item a row). pt_ref [B, Pmax] / len_ref [B] / layer_ref [1] SMEM; q_ref
    [1, Hq, lanes]; pool_ref the whole stacked pool, where it lives; o_ref
    [1, Hq, rank]; the rest :func:`_walk_scratch`."""
    b = pl.program_id(0)
    n_rows, n_pages = pt_ref.shape
    walk = _Walk(pt_ref, layer_ref, pool_ref, ring_ref, sem, walk_ref,
                 n_items=n_rows, trip=trip, page_size=page_size,
                 idle=lambda row: len_ref[row] == 0,
                 span=lambda row: page_span(len_ref[row], page_size, n_pages,
                                            sliding_window),
                 row=lambda row: row, unroll=True)

    @pl.when(b == 0)
    def _open():
        walk.open()

    length = len_ref[b]

    @pl.when(length == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def visible(k_pos):
        mask = k_pos < length
        if sliding_window is not None:      # the query sits at length - 1
            mask &= k_pos >= length - sliding_window
        return mask

    @pl.when(length > 0)
    def _busy():
        def attend(slot, k_start, *, pages, first):
            _attend_trip(q_ref[0], ring_ref, slot, k_start, visible, acc_ref,
                         m_ref, l_ref, pages=pages, page_size=page_size,
                         rank=rank, scale=scale, first=first)

        walk.run(b, attend)
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret",
                                             "sliding_window", "name",
                                             "trip"))
def mla_decode_attention(
    q: jnp.ndarray,           # [B, Hq, lanes] absorbed query, one a slot
    pool: jnp.ndarray,        # [L, N, page, lanes] the stacked latent pool
    page_table: jnp.ndarray,  # [B, Pmax] each slot's pages
    lengths: jnp.ndarray,     # [B] valid length (incl. current token)
    layer: jnp.ndarray | int = 0,
    *,
    rank: int,
    scale: float,
    interpret: bool | pltpu.InterpretParams = False,
    sliding_window: int | None = None,
    name: str | None = None,             # the call site's, in a device trace
    trip: int | None = None,             # a test's or a probe's pages a trip
) -> jnp.ndarray:
    """Returns ``[B, Hq, rank]``: each head's softmax-weighted sum of the
    compressed rows of its slot's pages in layer ``layer`` (the caller
    applies ``W_uv``). One program a slot, in order; the pool stays where it
    lives and the programs copy the pages of a row's span (``page_span``)
    themselves, :func:`trip_pages` at a time."""
    B, Hq, width = q.shape
    _, _, page_size, _ = pool.shape
    trip = trip or trip_pages(page_size, sliding_window)

    def at_row(i, pt, ln, ly):
        return (i, 0, 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, trip=trip,
                          rank=rank, scale=scale,
                          sliding_window=sliding_window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((1, Hq, width), at_row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, Hq, rank), at_row),
            scratch_shapes=_walk_scratch(trip, page_size, width, pool.dtype,
                                         Hq, rank)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool)


def ragged_q_block(width: int) -> int:
    """Queries a program of the ragged kernel takes: whole sublane tiles of
    a 16-bit block, and as many as keep the accumulator a few MB."""
    return min(32, width)


#: keys a trip of the ragged kernel takes at most: both dots at full MXU
#: tiles (128 score columns, a contraction of 128 in the value dot) and the
#: accumulator's rescale once a trip (PERF.md, PR 53, has the probe)
RAGGED_TRIP_KEYS = 256


def ragged_trip_pages(page_size: int, sliding_window: int | None,
                      q_block: int) -> int:
    """Pages a trip of the ragged kernel takes, from shapes: the pages of
    ``RAGGED_TRIP_KEYS`` keys (no more than the decode kernel's), and no
    more than a q-block's windows span, so that a window layer's program is
    one trip."""
    pages = min(TRIP_PAGES, max(1, RAGGED_TRIP_KEYS // page_size))
    if sliding_window is None:
        return pages
    return min(pages, _window_pages(sliding_window, page_size, q_block))


def ragged_span(hist, q_lens, width: int, page_size: int, n_pages: int,
                sliding_window: int | None):
    """(first, last) logical page every q-block of lanes ``width`` queries
    wide reads, ``[R, q_blocks]``: up to the page of the block's last real
    query's own key (``hist + min(q_lens, q0 + q_block) - 1``), from the
    page that holds the first key its first query sees (its length is its
    position + 1). A q-block past its lane's span reads nothing: ``last =
    first - 1``. ``jax.numpy`` arrays where the kernel's wrapper works the
    spans out, NumPy arrays where the host counts what the kernel walks."""
    q_block = ragged_q_block(width)
    q0 = np.arange(0, width, q_block, dtype=np.int32)
    hist, q_lens = hist[:, None], q_lens[:, None]
    q_hi = hist + q_lens.clip(max=q0 + q_block) - 1
    last = (q_hi // page_size).clip(0, n_pages - 1)
    first = _span_first(hist + q0 + 1, page_size, last, sliding_window)
    return first, last - (q0 >= q_lens) * (last - first + 1)


def ragged_walk(hist, q_lens, width: int, page_size: int, n_pages: int,
                sliding_window: int | None, trip: int | None = None
                ) -> tuple[int, int]:
    """(pages, trips) a call of :func:`mla_ragged_attention` over lanes of
    ``width`` queries walks: the copies it starts and the key blocks it
    attends over, by the kernel's own spans, on the host (NumPy)."""
    first, last = ragged_span(np.asarray(hist, np.int64),
                              np.asarray(q_lens, np.int64), width, page_size,
                              n_pages, sliding_window)
    trip = trip or ragged_trip_pages(page_size, sliding_window,
                                     ragged_q_block(width))
    return (int((last - first + 1).sum()),
            int(((last - first + trip) // trip).sum()))


def _ragged_kernel(pt_ref, first_ref, last_ref, hist_ref, qlen_ref, layer_ref,
                   q_ref, pool_ref, o_ref, ring_ref, sem, walk_ref, acc_ref,
                   m_ref, l_ref, *, page_size: int, q_block: int,
                   q_blocks: int, trip: int, rank: int, scale: float,
                   sliding_window: int | None):
    """One (lane, q-block): the program walks the pages its queries see
    itself (:class:`_Walk`, an item a (lane, q-block), lanes in order).
    q_ref [1, Hq, Qb, lanes], head-major, so its rows flatten to ``r = h*Qb
    + qi`` for nothing; the query at ``qi`` sits at ``hist + q0 + qi`` and
    sees the keys up to itself (the last ``sliding_window`` of them).
    pt_ref [R, Pmax] / first_ref, last_ref [R * q_blocks] (an item's span,
    :func:`ragged_span`; one that reads nothing has ``last < first``) /
    hist_ref [R] / qlen_ref [R] / layer_ref [1] SMEM; pool_ref the whole
    stacked pool, where it lives; o_ref [1, Hq, Qb, rank]; the rest
    :func:`_walk_scratch` at ``Hq * Qb`` rows."""
    b, qb = pl.program_id(0), pl.program_id(1)
    Hq, lanes = q_ref.shape[1], q_ref.shape[3]
    item = b * q_blocks + qb
    walk = _Walk(pt_ref, layer_ref, pool_ref, ring_ref, sem, walk_ref,
                 n_items=first_ref.shape[0], trip=trip, page_size=page_size,
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
        q_idx = q0 + jax.lax.broadcasted_iota(
            jnp.int32, k_pos.shape, 0) % q_block
        mask = (q_idx < qlen) & (k_pos <= hist + q_idx)
        if sliding_window is not None:
            mask &= k_pos > hist + q_idx - sliding_window
        return mask

    @pl.when(q0 < qlen)
    def _busy():
        def attend(slot, k_start, *, pages, first):
            _attend_trip(q_ref[0].reshape(Hq * q_block, lanes), ring_ref,
                         slot, k_start, visible, acc_ref, m_ref, l_ref,
                         pages=pages, page_size=page_size, rank=rank,
                         scale=scale, first=first)

        walk.run(item, attend)
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).reshape(
            Hq, q_block, rank).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret",
                                             "sliding_window", "name",
                                             "trip"))
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
    interpret: bool | pltpu.InterpretParams = False,
    sliding_window: int | None = None,
    name: str | None = None,
    trip: int | None = None,             # a test's or a probe's pages a trip
) -> jnp.ndarray:
    """A prompt's chunk over latent pages, absorbed like the decode kernel:
    each lane's span of ``q_lens`` queries attends causally over its own
    history and the span itself, which the caller has already written to
    the pool. Returns ``[R, Hq, Qc, rank]``; positions past a lane's span
    are zeros. One program a (lane, block of :func:`ragged_q_block`
    queries), in order; the pool stays where it lives and the programs copy
    the pages of a q-block's span themselves, :func:`ragged_trip_pages` at a
    time. The spans (:func:`ragged_span`) are worked out here, once a call,
    and ride in as scalar-prefetch operands: a program reads its own and the
    next ones' (whose copies it starts) instead of computing them."""
    R, Hq, Qc, width = q.shape
    _, _, page_size, _ = pool.shape
    q_block = ragged_q_block(Qc)
    if Qc % q_block or q_block % 16:
        raise ValueError(f"a chunk of {Qc} queries is not whole blocks of "
                         f"{q_block} (multiples of 16)")
    trip = trip or ragged_trip_pages(page_size, sliding_window, q_block)
    hist, q_lens = hist.astype(jnp.int32), q_lens.astype(jnp.int32)
    first, last = ragged_span(hist, q_lens, Qc, page_size,
                              page_table.shape[1], sliding_window)

    def at_block(b, qb, *_):
        return (b, 0, qb, 0)

    return pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size,
                          q_block=q_block, q_blocks=Qc // q_block, trip=trip,
                          rank=rank, scale=scale,
                          sliding_window=sliding_window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(R, Qc // q_block),
            in_specs=[pl.BlockSpec((1, Hq, q_block, width), at_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, Hq, q_block, rank), at_block),
            scratch_shapes=_walk_scratch(trip, page_size, width, pool.dtype,
                                         Hq * q_block, rank)),
        out_shape=jax.ShapeDtypeStruct((R, Hq, Qc, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), first.reshape(-1), last.reshape(-1),
      hist, q_lens, jnp.asarray(layer, jnp.int32).reshape(1), q, pool)
