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

**The decode kernel walks a row's pages inside ONE program** (PR 49; a grid
step a group of pages before). ``grid=(B,)``, in order; the pool is left
where it lives and the page table and the lengths are scalar-prefetch
operands. A **trip** takes up to :func:`trip_pages` consecutive pages of the
row's span (``page_span``: what the scheduler's walked/offered counters
count), one DMA a page and only for the pages inside the span, into one key
block of a ring of ``RING_BLOCKS``, where they land as the rows of one
``[trip * page, lanes]`` block: one score dot, one mask by position, one
online-softmax update, one value dot. The trips of a call are one sequence
(slots in order, a slot that holds nothing has none) and the ring runs
through it across the programs: the copies of the trips after the one being
attended over are in flight, so a row's last trips start the next row's
first. A group of pages is a trip: ``llm_attn_page_groups_total`` counts
those.

**A window** (``sliding_window``, static; ``models/motif.py``'s window
layers): a query at ``t`` sees the keys ``t - window < s <= t``. Both kernels
start a row at the first page of that span (``paged_attention._span_first``)
and mask the rest, so what a row costs does not grow with its length (the
decode kernel: one trip of the pages a window spans), and the pages left of
the span are never read: the pool may have given them to another row.
Without a window both give what they gave before PR 48, bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
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


def _attend_trip(q_ref, ring_ref, slot, k_start, length, acc_ref, m_ref,
                 l_ref, *, pages: int, page_size: int, rank: int,
                 scale: float, sliding_window: int | None, first: bool):
    """One trip of the decode kernel's walk: the first ``pages`` pages of
    key block ``slot`` are the rows of ONE key/value block whose first key
    sits at ``k_start``. A page is read once and is the key (all its lanes)
    and the value (its first ``rank``). ``first``: the row's first trip."""
    keys = pl.ds(0, pages * page_size)
    scores = jax.lax.dot_general(
        q_ref[0], ring_ref[slot, keys], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale         # [Hq, keys]
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    mask = k_pos < length
    if sliding_window is not None:          # the query sits at length - 1
        mask &= k_pos >= length - sliding_window
    _online_softmax_step(scores, mask, ring_ref[slot, keys, pl.ds(0, rank)],
                         acc_ref, m_ref, l_ref, first)


def _decode_kernel(pt_ref, len_ref, layer_ref, q_ref, pool_ref, o_ref,
                   ring_ref, sem, walk_ref, acc_ref, m_ref, l_ref, *,
                   page_size: int, trip: int, rank: int, scale: float,
                   sliding_window: int | None):
    """One slot: the program walks its row's span itself, ``trip`` pages a
    trip. pt_ref [B, Pmax] / len_ref [B] / layer_ref [1] SMEM; q_ref [1, Hq,
    lanes]; pool_ref the whole stacked pool, where it lives; o_ref [1, Hq,
    rank]; ring_ref [blocks, trip * page, lanes] the key blocks, ``sem`` a
    DMA semaphore each; acc [Hq, rank] f32; m/l [Hq, LANES] f32.

    The call's trips are ONE sequence (rows in order, a row's trips
    ascending, a row that holds nothing has none) and the ring runs through
    it across the programs, which run in order: while a trip is attended
    over, the ``blocks - 1`` after it are in flight. walk_ref [3] SMEM
    carries (the key block of the next trip to attend over, the row and the
    number of the next trip to START) from a program to the next.

    Every copy started has exactly one wait: the trip ``(row, j)`` is waited
    for by program ``row`` at its ``j``-th trip, under the condition it was
    started under (the page lies in the span)."""
    b = pl.program_id(0)
    n_rows, n_pages = pt_ref.shape
    blocks = ring_ref.shape[0]

    def span(row):
        start, last = page_span(len_ref[row], page_size, n_pages,
                                sliding_window)
        return start, last, (last - start) // trip + 1

    def copies(row, start, last, j, slot, do):
        """``do`` (start or wait) the copy of every page of trip ``j`` of
        ``row`` that lies in its span, into key block ``slot``: a spare page
        moves no bytes."""
        for t in range(trip):
            page = start + j * trip + t

            @pl.when(page <= last)
            def _():
                do(pltpu.make_async_copy(
                    pool_ref.at[layer_ref[0], pt_ref[row, page]],
                    ring_ref.at[slot, pl.ds(t * page_size, page_size)],
                    sem.at[slot]))

    def next_busy(row):
        """The first row at or after ``row`` that holds tokens; ``n_rows``
        where none does."""
        return jax.lax.while_loop(
            lambda r: (r < n_rows) & (len_ref[jnp.minimum(r, n_rows - 1)]
                                      == 0),
            lambda r: r + 1, jnp.minimum(row, n_rows))

    def start_next(slot, row, j):
        """Start the trip ``(row, j)`` (none: ``row`` is ``n_rows``) into
        key block ``slot``; returns the trip after it."""
        at = jnp.minimum(row, n_rows - 1)
        start, last, trips = span(at)

        @pl.when(row < n_rows)
        def _():
            copies(at, start, last, j, slot, lambda c: c.start())

        return jax.lax.cond(j + 1 < trips, lambda: (row, j + 1),
                            lambda: (next_busy(row + 1), jnp.zeros_like(j)))

    def wrap(slot):
        return jnp.where(slot >= blocks, slot - blocks, slot)

    @pl.when(b == 0)
    def _open():
        # a row of a key block no trip has written yet must hold numbers: a
        # zero probability times a NaN is a NaN in the value dot. After this
        # a block holds zeros or pages of some span, which the mask drops
        ring_ref[...] = jnp.zeros_like(ring_ref)
        nxt = (next_busy(0), jnp.int32(0))
        for slot in range(blocks - 1):
            nxt = start_next(slot, *nxt)
        walk_ref[0] = 0
        walk_ref[1], walk_ref[2] = nxt

    length = len_ref[b]

    @pl.when(length == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _walk():
        start, last, trips = span(b)

        def one_trip(j, walk, first=False):
            slot, *nxt = walk
            # the copies of the trip ``blocks - 1`` on go out before this
            # one's are waited for
            nxt = start_next(wrap(slot + blocks - 1), *nxt)
            copies(b, start, last, j, slot, lambda c: c.wait())
            held = jnp.minimum(last - (start + j * trip) + 1, trip)
            sizes = _block_sizes(trip)
            for under, pages in zip((0, *sizes), sizes):

                @pl.when((held > under) & (held <= pages))
                def _():
                    _attend_trip(q_ref, ring_ref, slot,
                                 (start + j * trip) * page_size, length,
                                 acc_ref, m_ref, l_ref, pages=pages,
                                 page_size=page_size, rank=rank, scale=scale,
                                 sliding_window=sliding_window, first=first)
            return (wrap(slot + 1), *nxt)

        # a row's first trip finds nothing in the accumulators and reads
        # nothing from them: where a row is one trip (a window's) that is
        # the whole of it
        walk = one_trip(0, (walk_ref[0], walk_ref[1], walk_ref[2]),
                        first=True)
        walk = jax.lax.fori_loop(1, trips, one_trip, walk)
        walk_ref[0], walk_ref[1], walk_ref[2] = walk
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
            scratch_shapes=[
                pltpu.VMEM((RING_BLOCKS, trip * page_size, width),
                           pool.dtype),
                pltpu.SemaphoreType.DMA((RING_BLOCKS,)),
                pltpu.SMEM((3,), jnp.int32),
                pltpu.VMEM((Hq, rank), jnp.float32),
                pltpu.VMEM((Hq, _LANES), jnp.float32),
                pltpu.VMEM((Hq, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool)


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
        Pmax, _window_pages(sliding_window, page_size, q_block))

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
