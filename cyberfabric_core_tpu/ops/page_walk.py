"""A kernel's walk over the pages of a paged cache, inside the program.

What the four kernels that copy their pages themselves share: the latent
kernels (``ops/mla_attention.py``: one pool, a page read once as key and as
value) and the K/V kernels (``ops/paged_attention.py``, the decode kernel
since PR 57: two pools that share a page id). The pools are left where they live and the page table, the spans
and the layer are scalar-prefetch operands. A call's work is a sequence of
ITEMS, one a program, in order: a decode kernel's rows (``grid=(B,)``), a
ragged kernel's (lane, block of queries) pairs
(``grid=(R, Qc // q_block)``). A **trip** takes consecutive pages of an
item's span (:func:`page_span` / :func:`ragged_span`: what the scheduler's
walked counters count), one DMA a page and a pool and only for the pages
inside the span, into one key block of a ring of ``RING_BLOCKS``, where they
land as the rows of one ``[trip * page, lanes]`` block: one score dot, one
mask by position, one online-softmax update, one value dot, over the
smallest block that holds what the trip copied (:func:`_block_sizes`). The
trips of a call are one sequence (items in order, an item that holds nothing
has none) and the ring runs through it across the programs: the copies of
the trips after the one being attended over are in flight, so an item's last
trips start the next item's first.
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

_NEG_INF = -1e30
_LANES = 128

#: VMEM a walking ragged kernel asks for: its accumulators are a few MB (2 048
#: to 2 560 rows of 128 numbers and of the running max and sum), beside the
#: ring and one trip's score block
_VMEM_LIMIT = 64 * 1024 * 1024


def page_span(length, page_size: int, n_pages: int,
              sliding_window: int | None):
    """(first, last) logical page a decode query at position ``length - 1``
    reads, of a table of ``n_pages`` slots: up to the page of its own token,
    from the page that holds the window's first key. An empty slot has the
    span (0, 0). Scalars in the kernel, NumPy arrays where the host counts
    what a kernel walked."""
    last = ((length - 1) // page_size).clip(0, n_pages - 1)
    return _span_first(length, page_size, last, sliding_window), last


def _span_first(length, page_size: int, last, sliding_window: int | None):
    """The first page of the span that ends at page ``last``."""
    if sliding_window is None:
        return last * 0
    # keys <= length - 1 - window are out, so page j is in while
    # (j + 1) * page_size > length - window
    return ((length - sliding_window) // page_size).clip(0, last)


def _online_softmax_step(scores, mask, weigh, acc_ref, m_ref, l_ref,
                         first: bool = False):
    """One block of keys of the flash recurrence: ``scores`` [R, keys] f32
    (already scaled), ``mask`` its visible keys, ``weigh(p)`` the block's
    values under the probabilities ``p`` [R, keys] f32 (the kernel's value
    dot, in the precision it keeps). ``first`` (static): the accumulators
    hold nothing yet and are not read; what is written is what the
    recurrence gives from its start (m at the floor, l and acc zero), bit
    for bit."""
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
    pv = weigh(p)
    if first:
        l_ref[...] = l_blk
        acc_ref[...] = pv
        return
    # a row with no visible key yet sits at the floor: it carries no mass
    # (the compare and not ``isfinite``, which has no Pallas TPU lowering:
    # a masked score is exactly the floor, never -inf)
    correction = jnp.where(m_new > _NEG_INF * 0.5,
                           jnp.exp(m_prev - m_new), 0.0)
    l_ref[...] = l_ref[...] * correction + l_blk
    acc_ref[...] = acc_ref[...] * correction[:, :1] + pv


#: pages a trip takes at most (the latent decode kernel's: the key block of
#: one score dot and one update of the accumulator). A trip's fixed cost (the
#: accumulator's rescale, the waits) is about what 4 pages' dots cost, and
#: what a row's last trip holds under 16 is attended over as a smaller block
#: (:func:`_block_sizes`): PERF.md, PR 49, has the probe that read 8 / 16 /
#: 32 at five shapes (16 is 11-15% under 8 and 2-5% under 32)
TRIP_PAGES = 16

#: key blocks of a ring: the one a trip attends over and the trips whose
#: copies are in flight behind it. With one in flight a copy has a trip's
#: body to arrive in, and takes about that long itself, so every trip waited
#: out the DMA's latency (PERF.md, PR 49: the probe at 2 and 3)
RING_BLOCKS = 3


def _window_pages(sliding_window: int, page_size: int,
                  queries: int = 1) -> int:
    """The most pages the window of ``queries`` consecutive positions spans
    (``ModelConfig.window_pages``, for a kernel that has no configuration)."""
    return (sliding_window + queries - 3) // page_size + 2


def decode_trip_pages(page_size: int, sliding_window: int | None,
                      most: int = TRIP_PAGES) -> int:
    """Pages a trip of a decode kernel takes, from shapes: ``TRIP_PAGES``
    (no more than ``most``, the kernel's own bound), and no more than a
    window spans."""
    pages = min(TRIP_PAGES, most)
    if sliding_window is None:
        return pages
    return min(pages, _window_pages(sliding_window, page_size))


def _block_sizes(trip: int) -> tuple[int, ...]:
    """The key blocks a trip of the latent kernels is attended over as, in
    pages: the powers of two from 2 (128 keys: a lane tile of scores) below
    ``trip``, and ``trip``. A trip takes the smallest that holds its pages,
    so a row's last trip, a window's two pages and a row that holds one
    token do not pay for the dots of a whole block."""
    sizes = []
    size = 2
    while size < trip:
        sizes.append(size)
        size *= 2
    return (*sizes, trip)


@dataclasses.dataclass(frozen=True)
class _Walk:
    """The walk every such kernel makes. A call's work is a sequence of ITEMS
    (a decode kernel's rows; a ragged kernel's (lane, q-block) pairs), one a
    program, and the programs run in order. An item reads the logical pages
    ``span(item)`` of row ``row(item)`` of the page table, ``trip`` pages a
    trip, one DMA a page and a pool and only for the pages inside the span;
    an item that is ``idle`` has no trips. The call's trips are ONE sequence
    (items in order, an item's trips ascending) and the rings of key blocks
    run through it across the programs: while a trip is attended over, the
    ``blocks - 1`` after it are in flight. walk_ref [3] SMEM carries (the key
    block of the next trip to attend over, the item and the number of the
    next trip to START) from a program to the next.

    Every copy started has exactly one wait: the trip ``(item, j)`` is
    waited for by program ``item`` at its ``j``-th trip, under the condition
    it was started under (the page lies in the span)."""
    pt_ref: Any         # [rows, Pmax] SMEM: the page table
    layer_ref: Any      # [1] SMEM
    pools: tuple        # the stacked pools, where they live: one page id
    rings: tuple        # [blocks, trip * page, lanes] VMEM, one a pool
    sem: Any            # a DMA semaphore a key block
    walk_ref: Any       # [3] SMEM
    n_items: int
    trip: int
    #: the key blocks a trip is attended over as, in pages, ascending to
    #: ``trip``: a trip takes the smallest that holds its pages. Each is two
    #: traced bodies of the kernel's attend (an item's first trip, a later
    #: one), in every program the kernel sits in
    sizes: tuple
    page_size: int
    idle: Callable      # item -> it holds nothing
    span: Callable      # item -> (first, last) logical page it reads
    row: Callable       # item -> its row of the page table
    #: the copies of a trip written out under a condition a page and an
    #: item's first trip peeled off the loop (the latent decode kernel: the
    #: form PR 49 measured; as loops its 16 copies a trip read 7.5-13.6%
    #: slower alone), or both as loops (the ragged kernels: a trip's body is
    #: 2 000 rows of dots and does not feel them, and a kernel is traced and
    #: lowered in every program it sits in, six widths of ``mixed_step`` a
    #: server: half the seconds an instance). PERF.md, PR 53.
    unroll: bool

    @property
    def blocks(self) -> int:
        return self.rings[0].shape[0]

    # The bookkeeping below is scalar arithmetic written in ``lax``
    # primitives, not operators: under a kernel's trace every ``jnp``
    # operator is a nested jit of its own, 0.3-0.4 ms of Python each, and a
    # kernel is traced and lowered again in every program it sits in (six
    # widths of ``mixed_step`` a server; PERF.md, PR 53).

    def _trips(self, item):
        """(first, last, trips) of ``item``'s span; ``last = first - 1``
        (a ragged kernel's idle item) is no trip."""
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
        ``item`` that lies in its span, out of every pool into its key block
        ``slot``: a spare page moves no bytes."""
        row, first = self.row(item), self._page(start, j)

        def copy(t):
            at = t * self.page_size if self.unroll else pl.multiple_of(
                lax.mul(t, self.page_size), self.page_size)
            for pool_ref, ring_ref in zip(self.pools, self.rings):
                do(pltpu.make_async_copy(
                    pool_ref.at[self.layer_ref[0],
                                self.pt_ref[row, lax.add(first, t)]],
                    ring_ref.at[slot, pl.ds(at, self.page_size)],
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

    def open(self, fill: tuple | None = None):
        """The call's first program: the first ``blocks - 1`` trips go out.
        ``fill``: the rings read as VALUES (absent: every ring)."""
        # a row of a key block no trip has written yet must hold numbers
        # where it is a value: a zero probability times a NaN is a NaN in
        # the value dot (a key's score is replaced under the mask, whatever
        # it is). After this a block holds zeros or pages of some span,
        # which the mask drops
        for ring_ref in self.rings if fill is None else fill:
            ring_ref[...] = jnp.zeros_like(ring_ref)
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
        pages=, first=)`` over each, as the smallest block of ``sizes`` that
        holds what the trip copied. ``first``: an item's first
        trip finds nothing in the accumulators and reads nothing from them;
        where an item is one trip (a window's) that is the whole of it."""
        walk_ref = self.walk_ref
        start, last, trips = self._trips(item)
        sizes = self.sizes

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
                  rank: int, pools: int = 1) -> list:
    """What a walking kernel keeps between its programs: a ring a pool, the
    semaphores, the cursor, and the flash accumulators of ``rows`` query
    rows (acc [rows, rank], m / l [rows, LANES], float32)."""
    ring = pltpu.VMEM((RING_BLOCKS, trip * page_size, width), dtype)
    return [*[ring] * pools,
            pltpu.SemaphoreType.DMA((RING_BLOCKS,)),
            pltpu.SMEM((3,), jnp.int32),
            pltpu.VMEM((rows, rank), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32)]


# ---- a ragged kernel's items: (lane, block of queries)

def ragged_trip_pages(page_size: int, sliding_window: int | None,
                      q_block: int, keys: int) -> int:
    """Pages a trip of a ragged kernel takes, from shapes: the pages of
    ``keys`` keys (the kernel's own: what one score dot's rows make worth
    while; no more than ``TRIP_PAGES``), and no more than a q-block's
    windows span, so that a program behind a short window is one trip."""
    pages = min(TRIP_PAGES, max(1, keys // page_size))
    if sliding_window is None:
        return pages
    return min(pages, _window_pages(sliding_window, page_size, q_block))


def ragged_span(hist, q_lens, width: int, page_size: int, n_pages: int,
                sliding_window: int | None, q_block: int, block: int = 1):
    """(first, last) logical page every block of ``q_block`` queries of
    lanes ``width`` wide reads, ``[R, q_blocks]``: up to the page of the
    block's last real query's own key (``hist + min(q_lens, q0 + q_block) -
    1``; under a block mask the last key of that query's block of ``block``
    positions), from the page that holds the first key its first query sees
    (its length is its position + 1). A q-block past its lane's span reads
    nothing: ``last = first - 1``. ``jax.numpy`` arrays where a kernel's
    wrapper works the spans out, NumPy arrays where the host counts what the
    kernel walks."""
    q0 = np.arange(0, width, q_block, dtype=np.int32)
    hist, q_lens = hist[:, None], q_lens[:, None]
    q_hi = hist + q_lens.clip(max=q0 + q_block) - 1
    if block > 1:
        q_hi = q_hi | (block - 1)
    last = (q_hi // page_size).clip(0, n_pages - 1)
    first = _span_first(hist + q0 + 1, page_size, last, sliding_window)
    return first, last - (q0 >= q_lens) * (last - first + 1)


def ragged_walk(hist, q_lens, width: int, page_size: int, n_pages: int,
                sliding_window: int | None, q_block: int, trip: int,
                block: int = 1) -> tuple[int, int]:
    """(pages, trips) a call of a ragged kernel over lanes of ``width``
    queries walks, ``q_block`` queries a program and ``trip`` pages a trip:
    the copies it starts in a pool and the key blocks it attends over, by
    the kernel's own spans, on the host (NumPy)."""
    first, last = ragged_span(np.asarray(hist, np.int64),
                              np.asarray(q_lens, np.int64),
                              -(-width // q_block) * q_block, page_size,
                              n_pages, sliding_window, q_block, block)
    return (int((last - first + 1).sum()),
            int(((last - first + trip) // trip).sum()))
