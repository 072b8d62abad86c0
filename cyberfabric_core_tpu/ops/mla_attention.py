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

**Both kernels walk their pages inside the program** (``ops/page_walk.py``:
:class:`_Walk`, which the ragged K/V kernel shares since PR 55; the decode
kernel since PR 49, a grid step a group of pages before; the ragged kernel
since PR 53, a grid step a page before). The pool is left where it lives;
an item is a decode row (``grid=(B,)``) or a (lane, block of
:func:`ragged_q_block` queries) pair (``grid=(R, Qc // q_block)``); a
**trip** of consecutive pages of an item's span lands in one key block of a
ring as the rows of one ``[trip * page, lanes]`` block: one score dot, one
mask by position, one online-softmax update, one value dot.

The kernels differ in how many positions a program's queries have. A decode
row is one query at ``length - 1`` and takes :func:`trip_pages` pages a trip
(16; ``llm_attn_page_groups_total`` counts its trips). A q-block is ``q_block``
queries, head-major rows ``h * q_block + qi`` at ``hist + q0 + qi``, masked
by query (causal, and the window's other edge); its trip is
:func:`ragged_trip_pages` pages (4 of 64 tokens: a key block of 256 keys, so
both dots run at full 128-wide MXU tiles and the accumulator, 4-5 MB at
64-80 heads, is rescaled once a trip; ``llm_ragged_trips_total`` counts
them).

**A chosen set** (``keep``; ``ops/dsa.py``): both kernels mask by position,
and also by choice where the caller hands them the keys each query attends
(1 or 0 a query and key, laid out by the kernel's own trips). The walk does
not change: every page of the span is copied and scored, and a key that was
not chosen is masked like one the position rules out. (Gathering a decode
row's chosen rows in front of the kernel instead costs 29 ns a row gathered
whatever it moves, 1.9 ms a call at 32 rows of 2048: PERF.md, PR 58.)

**A window** (``sliding_window``, static; ``models/motif.py``'s window
layers): a query at ``t`` sees the keys ``t - window < s <= t``. Both kernels
start an item at the first page of that span (``page_walk._span_first``)
and mask the rest, so what an item costs does not grow with its length (one
trip of the pages a window, or a q-block's windows, span: no second pass over
the accumulator), and the pages left of the span are never read: the pool may
have given them to another row. Without a window the decode kernel gives
what it gave before PR 48, bit for bit, and the ragged kernel at ``trip=1``
what it gave before PR 53 (at its own trip: another order of the same sums).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import page_walk
from .page_walk import (TRIP_PAGES, _VMEM_LIMIT, _Walk, _block_sizes,
                        _online_softmax_step, _walk_scratch, _window_pages,
                        page_span, ragged_span)


def trip_pages(page_size: int, sliding_window: int | None) -> int:
    """Pages a trip of the decode kernel takes, from shapes: ``TRIP_PAGES``,
    and no more than a window spans."""
    return page_walk.decode_trip_pages(page_size, sliding_window)


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
    mask = visible(k_pos)
    value = ring_ref[slot, keys, pl.ds(0, rank)]
    _online_softmax_step(
        scores, mask,
        lambda p: jax.lax.dot_general(p.astype(value.dtype), value,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32),
        acc_ref, m_ref, l_ref, first)


def _decode_kernel(pt_ref, len_ref, layer_ref, q_ref, pool_ref, *rest,
                   page_size: int, trip: int, rank: int, scale: float,
                   sliding_window: int | None, chosen: bool = False):
    """One slot: the program walks its row's span itself (:class:`_Walk`, an
    item a row). pt_ref [B, Pmax] / len_ref [B] / layer_ref [1] SMEM; q_ref
    [1, Hq, lanes]; pool_ref the whole stacked pool, where it lives; o_ref
    [1, Hq, rank]; the rest :func:`_walk_scratch`. ``chosen`` (static):
    ahead of o_ref comes keep_ref [1, trips, trip keys] int32, row ``j`` the
    keys of the row's ``j``-th trip that the query attends (1) or leaves out
    (0)."""
    keep_ref = rest[0] if chosen else None
    o_ref, ring_ref, sem, walk_ref, acc_ref, m_ref, l_ref = rest[chosen:]
    b = pl.program_id(0)
    n_rows, n_pages = pt_ref.shape
    walk = _Walk(pt_ref, layer_ref, (pool_ref,), (ring_ref,), sem, walk_ref,
                 n_items=n_rows, trip=trip, sizes=_block_sizes(trip),
                 page_size=page_size,
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
            seen = visible
            if chosen:      # without a window a span starts at page 0
                tile = keep_ref[0, pl.ds(lax.div(k_start, trip * page_size),
                                         1), :]
                kept = tile[:, : pages * page_size] > 0     # [1, keys]

                def seen(k_pos):
                    return visible(k_pos) & kept

            _attend_trip(q_ref[0], ring_ref, slot, k_start, seen, acc_ref,
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
    keep: jnp.ndarray | None = None,     # [B, Pmax * page] 1 / 0 a key
) -> jnp.ndarray:
    """Returns ``[B, Hq, rank]``: each head's softmax-weighted sum of the
    compressed rows of its slot's pages in layer ``layer`` (the caller
    applies ``W_uv``). One program a slot, in order; the pool stays where it
    lives and the programs copy the pages of a row's span (``page_span``)
    themselves, :func:`trip_pages` at a time.

    ``keep`` (``ops/dsa.py: keep_mask``; None: every key a position allows):
    the keys each row's query attends. The walk is the same (every page of
    the span is copied and scored); a key that was not chosen is masked like
    one past the row's length. The call without it lowers as it did before
    the operand existed."""
    B, Hq, width = q.shape
    _, _, page_size, _ = pool.shape
    trip = trip or trip_pages(page_size, sliding_window)

    def at_row(i, pt, ln, ly):
        return (i, 0, 0)

    chosen, extra = (), {}
    if keep is not None:
        if sliding_window is not None:
            raise ValueError("a chosen set goes with no sliding window")
        chosen, extra = (_by_trips(keep, page_table.shape[1], page_size,
                                   trip).astype(jnp.int32),), {"chosen": True}

    return pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, trip=trip,
                          rank=rank, scale=scale,
                          sliding_window=sliding_window, **extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((1, Hq, width), at_row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      *[pl.BlockSpec((1, *c.shape[1:]), at_row)
                        for c in chosen]],
            out_specs=pl.BlockSpec((1, Hq, rank), at_row),
            scratch_shapes=_walk_scratch(trip, page_size, width, pool.dtype,
                                         Hq, rank)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool, *chosen)


def _by_trips(keep: jnp.ndarray, n_pages: int, page_size: int,
              trip: int) -> jnp.ndarray:
    """``keep`` [..., Pmax * page] as ``[..., trips, trip keys]``: the keys of
    a span's ``j``-th trip a tile (zeros past the table's last page)."""
    trips = -(-n_pages // trip)
    tile = trip * page_size
    pad = [(0, 0)] * (keep.ndim - 1) + [(0, trips * tile - keep.shape[-1])]
    return jnp.pad(keep, pad).reshape(*keep.shape[:-1], trips, tile)


def ragged_q_block(width: int) -> int:
    """Queries a program of the ragged kernel takes: whole sublane tiles of
    a 16-bit block, and as many as keep the accumulator a few MB."""
    return min(32, width)


#: keys a trip of the ragged kernel takes at most: both dots at full MXU
#: tiles (128 score columns, a contraction of 128 in the value dot) and the
#: accumulator's rescale once a trip; every head attends over the one key
#: block, 2 048 rows a score dot, and 512 keys read twice as slow (PERF.md,
#: PR 53, has the probe)
RAGGED_TRIP_KEYS = 256


def ragged_trip_pages(page_size: int, sliding_window: int | None,
                      q_block: int) -> int:
    """Pages a trip of the ragged kernel takes (``page_walk``'s rule at
    ``RAGGED_TRIP_KEYS``): 4 of 64 tokens, and no more than a q-block's
    windows span, so that a window layer's program is one trip."""
    return page_walk.ragged_trip_pages(page_size, sliding_window, q_block,
                                       RAGGED_TRIP_KEYS)


def ragged_walk(hist, q_lens, width: int, page_size: int, n_pages: int,
                sliding_window: int | None, trip: int | None = None
                ) -> tuple[int, int]:
    """(pages, trips) a call of :func:`mla_ragged_attention` over lanes of
    ``width`` queries walks: the copies it starts and the key blocks it
    attends over, by the kernel's own spans, on the host (NumPy)."""
    q_block = ragged_q_block(width)
    return page_walk.ragged_walk(
        hist, q_lens, width, page_size, n_pages, sliding_window, q_block,
        trip or ragged_trip_pages(page_size, sliding_window, q_block))


def _ragged_kernel(pt_ref, first_ref, last_ref, hist_ref, qlen_ref, layer_ref,
                   q_ref, pool_ref, *rest, page_size: int, q_block: int,
                   q_blocks: int, trip: int, rank: int, scale: float,
                   sliding_window: int | None, chosen: bool = False):
    """One (lane, q-block): the program walks the pages its queries see
    itself (:class:`_Walk`, an item a (lane, q-block), lanes in order).
    q_ref [1, Hq, Qb, lanes], head-major, so its rows flatten to ``r = h*Qb
    + qi`` for nothing; the query at ``qi`` sits at ``hist + q0 + qi`` and
    sees the keys up to itself (the last ``sliding_window`` of them).
    pt_ref [R, Pmax] / first_ref, last_ref [R * q_blocks] (an item's span,
    :func:`ragged_span`; one that reads nothing has ``last < first``) /
    hist_ref [R] / qlen_ref [R] / layer_ref [1] SMEM; pool_ref the whole
    stacked pool, where it lives; o_ref [1, Hq, Qb, rank]; the rest
    :func:`_walk_scratch` at ``Hq * Qb`` rows. ``chosen`` (static): ahead of
    o_ref comes keep_ref [1, 1, trips, Qb, trip keys] int8, tile ``j`` the
    keys of the span's ``j``-th trip that each query attends (1) or leaves
    out (0): a key is visible where its position says so AND it was
    chosen."""
    keep_ref = rest[0] if chosen else None
    o_ref, ring_ref, sem, walk_ref, acc_ref, m_ref, l_ref = rest[chosen:]
    b, qb = pl.program_id(0), pl.program_id(1)
    Hq, lanes = q_ref.shape[1], q_ref.shape[3]
    item = b * q_blocks + qb
    walk = _Walk(pt_ref, layer_ref, (pool_ref,), (ring_ref,), sem, walk_ref,
                 n_items=first_ref.shape[0], trip=trip,
                 sizes=_block_sizes(trip), page_size=page_size,
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
            seen = visible
            if chosen:      # without a window a span starts at page 0
                tile = keep_ref[0, 0, lax.div(k_start, trip * page_size)]
                kept = jnp.broadcast_to(
                    tile[:, : pages * page_size].astype(jnp.int32)[None],
                    (Hq, q_block, pages * page_size)).reshape(
                        Hq * q_block, pages * page_size) > 0

                def seen(k_pos):
                    return visible(k_pos) & kept

            _attend_trip(q_ref[0].reshape(Hq * q_block, lanes), ring_ref,
                         slot, k_start, seen, acc_ref, m_ref, l_ref,
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
    keep: jnp.ndarray | None = None,     # [R, Qc, Pmax * page] int8
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
    next ones' (whose copies it starts) instead of computing them.

    ``keep`` (``ops/dsa.py: keep_mask``; None: every key a position allows):
    the keys each query attends, 1 or 0 a (query, key), read inside the mask
    by position. It rides in laid out by the kernel's own trips (``[R,
    q-blocks, trips, Qb, trip keys]``, a program's block its q-block's
    tiles); the call without it lowers as it did before the operand
    existed."""
    R, Hq, Qc, width = q.shape
    _, _, page_size, _ = pool.shape
    q_block = ragged_q_block(Qc)
    if Qc % q_block or q_block % 16:
        raise ValueError(f"a chunk of {Qc} queries is not whole blocks of "
                         f"{q_block} (multiples of 16)")
    trip = trip or ragged_trip_pages(page_size, sliding_window, q_block)
    hist, q_lens = hist.astype(jnp.int32), q_lens.astype(jnp.int32)
    first, last = ragged_span(hist, q_lens, Qc, page_size,
                              page_table.shape[1], sliding_window, q_block)

    def at_block(b, qb, *_):
        return (b, 0, qb, 0)

    chosen, extra = (), {}
    if keep is not None:
        if sliding_window is not None:
            raise ValueError("a chosen set goes with no sliding window")
        tiles = _by_trips(keep.astype(jnp.int8), page_table.shape[1],
                          page_size, trip)
        chosen = (tiles.reshape(R, Qc // q_block, q_block, *tiles.shape[2:]
                                ).transpose(0, 1, 3, 2, 4),)
        extra = {"chosen": True}

    return pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size,
                          q_block=q_block, q_blocks=Qc // q_block, trip=trip,
                          rank=rank, scale=scale,
                          sliding_window=sliding_window, **extra),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(R, Qc // q_block),
            in_specs=[pl.BlockSpec((1, Hq, q_block, width), at_block),
                      pl.BlockSpec(memory_space=pl.ANY),
                      *[pl.BlockSpec((1, 1, *c.shape[2:]),
                                     lambda b, qb, *_: (b, qb, 0, 0, 0))
                        for c in chosen]],
            out_specs=pl.BlockSpec((1, Hq, q_block, rank), at_block),
            scratch_shapes=_walk_scratch(trip, page_size, width, pool.dtype,
                                         Hq * q_block, rank)),
        out_shape=jax.ShapeDtypeStruct((R, Hq, Qc, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), first.reshape(-1), last.reshape(-1),
      hist, q_lens, jnp.asarray(layer, jnp.int32).reshape(1), q, pool,
      *chosen)
