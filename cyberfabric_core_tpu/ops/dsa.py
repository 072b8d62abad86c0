"""Attention over a chosen set: the index pass, the exact selection, and the
mask by which the chosen keys reach the latent attention kernels.

A model whose queries attend over a chosen set (``ModelConfig.is_sparse``;
``models/glm_dsa.py``) caches, a token a layer, ONE index key ``kI`` of
``index_head_dim`` numbers beside the latent row, in ``index_pool [L, N,
page, index_lanes]`` under the latent chain's page ids. A query at ``t``
carries ``index_heads`` small query heads ``qI_i`` and a float32 weight a
head ``w_i``, and scores every key it may see:

    I(t, s) = sum_i w_i(t) relu(qI_i(t) . kI(s)),   s <= t        (float32)

It then attends over the ``index_topk`` keys of largest ``I`` (over all of
them while it sees no more than that), by TOKEN: with seeded weights the
chosen keys lie in every page, so a list of pages would read everything.

**The index pass** walks a row's index pages inside the program
(``ops/page_walk.py``: :class:`_Walk` over the one index pool), as the
latent kernels walk the latent pages: an item is a decode row
(:func:`index_scores`, the heads as the query rows of one score dot) or a
(lane, block of queries) pair (:func:`index_scores_ragged`, rows ``h *
q_block + qi``); a trip of :data:`INDEX_TRIP_PAGES` pages is one key block,
one dot, the relu, the weights and the sum over heads, so that what leaves
the program is one float32 number a (query, key), :data:`NEG` where the
query may not see the key or the row holds none. A trip's scores land in
their own tile of the output (``[.., trip, .., trip keys]``, the tile a
dynamic index on a leading dimension), which the wrapper lays out as ``[..,
S]``.

**The selection is exact and sorts nothing** (:func:`select`, the kernel
``dsa_select``): an approximate set would be another result, and what a step
needs of a row of scores is not its order but two numbers, the
``index_topk``-th largest score and the position that breaks the tie there
(the lower position wins, ``jax.lax.top_k``'s order among equals).
``lax.top_k`` at 2 048 of 16 384 sorts the row: 0.32-0.45 ms for a decode
step's 32 rows, 0.5-4.5 ms for a chunk's 512 queries by its span, 12-18% of
the glm cell's device time. The kernel reads a block of rows into VMEM once
and bisects on the scores' bits, 32 rounds of a compare and a count along the
row, then 14 more over the position's: 13-22 us for the decode rows, 0.11-0.27
ms for a chunk (PERF.md, PR 59). The list of chosen positions, which the
benchmark's judge reads and no served step does, is made from the mask
(:func:`chosen_positions`) and is not computed where it is not returned.

**The attention behind it does not gather.** Both latent kernels
(``ops/mla_attention.py``) walk a span as they do and take one more operand,
:func:`keep_mask`: the keys each query chose, 1 or 0 a (query, key), read
inside the mask by position: ``[B, S]`` of a decode row, ``[R, Qc, S]`` of a
chunk. So a query ATTENDS its chosen keys and no others, and a row READS
every page of its span (1 280 B a key a layer), chosen or not. A gather of
the chosen rows in front of the decode kernel (``[B, topk, lanes]``, attended
as a pool of its own) was built first and measured: XLA's gather costs 29 ns
a row gathered whatever a row holds (a bfloat16 row is half of 32-bit words
that two tokens share), 1.9 ms a call at 32 rows of 2 048, what the masked
walk costs at 21k tokens a row; under that a walk is cheaper, and the cell's
rows hold 2-15k (PERF.md, PR 58).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .page_walk import RING_BLOCKS, _VMEM_LIMIT, _Walk, page_span, ragged_span

#: the score of a key a query may not see (and of a slot of the page table
#: that holds none): under every real score, and finite
NEG = -1e30

#: pages a trip of the index pass takes: 1 024 keys of 64-token pages, 256 KB
#: of index keys a copy round and a score block of [rows, 1 024] float32 (4
#: MB at a q-block's 1 024 query rows)
INDEX_TRIP_PAGES = 16

def _trip(n_pages: int) -> int:
    return min(INDEX_TRIP_PAGES, n_pages)


def _index_scratch(trip: int, page_size: int, width: int, dtype) -> list:
    """What a walking index kernel keeps between its programs: the ring of
    key blocks, its semaphores and the walk's cursor."""
    return [pltpu.VMEM((RING_BLOCKS, trip * page_size, width), dtype),
            pltpu.SemaphoreType.DMA((RING_BLOCKS,)),
            pltpu.SMEM((3,), jnp.int32)]


def _weighted_relu(q, w, ring_ref, slot):
    """``sum_h w_h relu(q_h . k)`` of one key block: ``q`` [rows, lanes]
    (rows ``h * queries + qi``), ``w`` [rows, 1] float32; returns [rows,
    keys] float32 BEFORE the sum over heads (the caller's rows differ)."""
    scores = lax.dot_general(q, ring_ref[slot], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return jnp.maximum(scores, 0.0) * w


def _decode_kernel(pt_ref, len_ref, layer_ref, q_ref, w_ref, pool_ref, o_ref,
                   ring_ref, sem, walk_ref, *, page_size: int, trip: int):
    """One decode row: q_ref [1, Hi, lanes], w_ref [1, Hi, 1] f32; o_ref [1,
    trips, trip keys] f32, tile ``j`` the scores of the row's ``j``-th
    trip."""
    b = pl.program_id(0)
    n_rows, n_pages = pt_ref.shape
    walk = _Walk(pt_ref, layer_ref, (pool_ref,), (ring_ref,), sem, walk_ref,
                 n_items=n_rows, trip=trip, sizes=(trip,),
                 page_size=page_size,
                 idle=lambda row: len_ref[row] == 0,
                 span=lambda row: page_span(len_ref[row], page_size, n_pages,
                                            None),
                 row=lambda row: row, unroll=False)

    @pl.when(b == 0)
    def _open():
        walk.open(fill=())      # a key's score is replaced under the mask

    length = len_ref[b]
    o_ref[...] = jnp.full(o_ref.shape, NEG, o_ref.dtype)

    @pl.when(length > 0)
    def _busy():
        def attend(slot, k_start, *, pages, first):
            del pages, first
            part = _weighted_relu(q_ref[0], w_ref[0], ring_ref, slot)
            scores = jnp.sum(part, axis=0, keepdims=True)       # [1, keys]
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            j = lax.div(k_start, trip * page_size)
            o_ref[0, pl.ds(j, 1), :] = jnp.where(k_pos < length, scores, NEG)

        walk.run(b, attend)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def index_scores(
    q: jnp.ndarray,           # [B, Hi, lanes] a row's index query heads
    w: jnp.ndarray,           # [B, Hi] float32 a head's weight
    pool: jnp.ndarray,        # [L, N, page, lanes] the stacked index pool
    page_table: jnp.ndarray,  # [B, Pmax]
    lengths: jnp.ndarray,     # [B] valid length (incl. current token)
    layer: jnp.ndarray | int = 0,
    *,
    interpret: bool | pltpu.InterpretParams = False,
    name: str | None = "dsa_index_scores",
) -> jnp.ndarray:
    """``I(t, s)`` of one decode query a row, ``[B, Pmax * page]`` float32:
    :data:`NEG` at the keys ``s >= length`` (an empty row: everywhere). One
    program a row, in order; the pool stays where it lives."""
    B, Hi, width = q.shape
    _, _, page_size, _ = pool.shape
    n_pages = page_table.shape[1]
    trip = _trip(n_pages)
    trips = -(-n_pages // trip)

    def at_row(i, *_):
        return (i, 0, 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, trip=trip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((1, Hi, width), at_row),
                      pl.BlockSpec((1, Hi, 1), at_row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, trips, trip * page_size), at_row),
            scratch_shapes=_index_scratch(trip, page_size, width,
                                          pool.dtype)),
        out_shape=jax.ShapeDtypeStruct((B, trips, trip * page_size),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q,
      w.astype(jnp.float32)[..., None], pool)
    return out.reshape(B, -1)[:, : n_pages * page_size]


def _ragged_kernel(pt_ref, first_ref, last_ref, hist_ref, qlen_ref, layer_ref,
                   q_ref, w_ref, pool_ref, o_ref, ring_ref, sem, walk_ref, *,
                   page_size: int, q_block: int, q_blocks: int, trip: int):
    """One (lane, q-block): q_ref [1, 1, Hi * Qb, lanes] (rows ``h * Qb +
    qi``), w_ref [1, 1, Hi * Qb, 1] f32; o_ref [1, 1, trips, Qb, trip keys]
    f32. The query at ``qi`` sits at ``hist + q0 + qi`` and sees the keys up
    to itself."""
    b, qb = pl.program_id(0), pl.program_id(1)
    item = b * q_blocks + qb
    heads = q_ref.shape[2] // q_block
    walk = _Walk(pt_ref, layer_ref, (pool_ref,), (ring_ref,), sem, walk_ref,
                 n_items=first_ref.shape[0], trip=trip, sizes=(trip,),
                 page_size=page_size,
                 idle=lambda item: last_ref[item] < first_ref[item],
                 span=lambda item: (first_ref[item], last_ref[item]),
                 row=lambda item: lax.div(item, q_blocks), unroll=False)

    @pl.when(item == 0)
    def _open():
        walk.open(fill=())

    hist, qlen = hist_ref[b], qlen_ref[b]
    q0 = qb * q_block
    o_ref[...] = jnp.full(o_ref.shape, NEG, o_ref.dtype)

    @pl.when(q0 < qlen)
    def _busy():
        def attend(slot, k_start, *, pages, first):
            del pages, first
            part = _weighted_relu(q_ref[0, 0], w_ref[0, 0], ring_ref, slot)
            keys = part.shape[1]
            scores = jnp.sum(part.reshape(heads, q_block, keys), axis=0)
            q_idx = q0 + lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            seen = (q_idx < qlen) & (k_pos <= hist + q_idx)
            j = lax.div(k_start, trip * page_size)
            o_ref[0, 0, j] = jnp.where(seen, scores, NEG)

        walk.run(item, attend)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def index_scores_ragged(
    q: jnp.ndarray,           # [R, Qc, Hi, lanes]
    w: jnp.ndarray,           # [R, Qc, Hi] float32
    pool: jnp.ndarray,        # [L, N, page, lanes]
    page_table: jnp.ndarray,  # [R, Pmax] the lanes' rows of the page table
    hist: jnp.ndarray,        # [R] tokens BEFORE each lane's span
    q_lens: jnp.ndarray,      # [R] span length (0 = idle lane)
    layer: jnp.ndarray | int = 0,
    *,
    interpret: bool | pltpu.InterpretParams = False,
    name: str | None = "dsa_index_scores_ragged",
) -> jnp.ndarray:
    """``I(t, s)`` of every query of the lanes' spans, ``[R, Qc, Pmax *
    page]`` float32: a query at ``hist + qi`` scores the keys up to itself,
    which the caller has already written to the pool; :data:`NEG` elsewhere
    and for the positions past a lane's span. One program a (lane, block of
    ``mla_attention.ragged_q_block`` queries), in order."""
    R, Qc, Hi, width = q.shape
    _, _, page_size, _ = pool.shape
    from .mla_attention import ragged_q_block

    n_pages = page_table.shape[1]
    q_block = ragged_q_block(Qc)    # the ragged latent kernel's: one span
    if Qc % q_block or q_block % 8:
        raise ValueError(f"a chunk of {Qc} queries is not whole blocks of "
                         f"{q_block} (multiples of 8)")
    q_blocks = Qc // q_block
    trip = _trip(n_pages)
    trips = -(-n_pages // trip)
    hist, q_lens = hist.astype(jnp.int32), q_lens.astype(jnp.int32)
    first, last = ragged_span(hist, q_lens, Qc, page_size, n_pages, None,
                              q_block)
    # head-major rows of a q-block, as the ragged latent kernel has them
    rows = q.reshape(R, q_blocks, q_block, Hi, width).transpose(
        0, 1, 3, 2, 4).reshape(R, q_blocks, Hi * q_block, width)
    w_rows = w.astype(jnp.float32).reshape(R, q_blocks, q_block, Hi).transpose(
        0, 1, 3, 2).reshape(R, q_blocks, Hi * q_block, 1)

    def at_block(b, qb, *_):
        return (b, qb, 0, 0)

    tile = trip * page_size
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size,
                          q_block=q_block, q_blocks=q_blocks, trip=trip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(R, q_blocks),
            in_specs=[pl.BlockSpec((1, 1, Hi * q_block, width), at_block),
                      pl.BlockSpec((1, 1, Hi * q_block, 1), at_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, trips, q_block, tile),
                                   lambda b, qb, *_: (b, qb, 0, 0, 0)),
            scratch_shapes=_index_scratch(trip, page_size, width,
                                          pool.dtype)),
        out_shape=jax.ShapeDtypeStruct((R, q_blocks, trips, q_block, tile),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), first.reshape(-1), last.reshape(-1),
      hist, q_lens, jnp.asarray(layer, jnp.int32).reshape(1), rows, w_rows,
      pool)
    return out.transpose(0, 1, 3, 2, 4).reshape(
        R, Qc, -1)[..., : n_pages * page_size]


#: rows a program of the selection takes at most (64 rows of 16 384 float32
#: scores are 4 MB, twice in flight) and the scores one step of a round
#: compares: 32 vector registers, 512 lanes of 64 rows or 1 024 of a decode
#: step's 32, which is also the grain a span is rounded up to. A round costs
#: a register about a cycle and a step a few more; at 64 registers a step
#: they spill (PERF.md, PR 59, has the probe)
SELECT_ROWS = 64
SELECT_STEP = 32 * 1024

_INT_MIN = -2 ** 31


def _score_of(key):
    """The float32 whose ORDER KEY is ``key`` (int32, any shape): a float's
    bits as a signed integer, a negative float's lower 31 bits flipped, rise
    with the float. A key between the infinities' and the integer's ends is
    a NaN, which no score compares at or over."""
    bits = jnp.where(key >= 0, key, key ^ 0x7FFFFFFF)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _select_kernel(span_ref, s_ref, kth_ref, at_ref, count_ref, *, k: int,
                   lanes: int):
    """A block of rows: s_ref [rows, S] f32 in VMEM; out, a row each over 128
    lanes, the k-th score, the k-th position and the count kept. Every round
    is one pass over the lane blocks under ``span_ref[0]``: a compare and a
    count a row, nothing written."""
    rows, width = s_ref.shape
    blocks = pl.cdiv(jnp.minimum(span_ref[0], width), lanes)
    lane = lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)

    def count(hit):
        """[rows, 1] f32: the keys under the span where ``hit(block [rows,
        lanes], its first lane)`` holds (a count is at most 16 384: exact)."""
        def step(j, acc):
            start = pl.multiple_of(j * lanes, lanes)
            took = jnp.where(hit(s_ref[:, pl.ds(start, lanes)], start),
                             1.0, 0.0)
            return acc + sum(took[:, i: i + 128]
                             for i in range(0, lanes, 128))

        acc = lax.fori_loop(0, blocks, step,
                            jnp.zeros((rows, 128), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the largest order key that k scores reach, a bit a round from the top
    # (the sign first): the k-th largest score, exactly
    def score_round(i, key):
        cand = key ^ lax.shift_left(jnp.int32(1), 31 - i)
        thr = _score_of(cand)
        return jnp.where(count(lambda s, _: s >= thr) >= k, cand, key)

    kth = _score_of(lax.fori_loop(
        0, 32, score_round, jnp.full((rows, 1), _INT_MIN, jnp.int32)))
    # the rounds end on the LARGEST key that compares equal to the k-th
    # score: among what the device reads as zero (either sign, and the
    # denormals it flushes) that is the largest denormal; hand on +0.0
    kth = jnp.where(kth == 0.0, 0.0, kth)
    binding = kth > NEG / 2         # NaN: the span holds under k lanes
    floor = jnp.where(binding, kth, NEG / 2)
    over = count(lambda s, _: s > floor)
    need = k - over                 # of the keys AT the k-th score

    # the position of the ``need``-th of them by rising position: the
    # largest ``at`` with fewer than ``need`` of them before it
    at_bits = max(1, (width - 1).bit_length())

    def position_round(i, at):
        cand = at | lax.shift_left(jnp.int32(1), at_bits - 1 - i)
        before = count(lambda s, start: (s == kth) & (lane < cand - start))
        return jnp.where(before < need, cand, at)

    at = lax.fori_loop(0, at_bits, position_round,
                       jnp.zeros((rows, 1), jnp.int32))
    kth_ref[...] = jnp.broadcast_to(jnp.where(binding, kth, NEG),
                                    kth_ref.shape)
    at_ref[...] = jnp.broadcast_to(jnp.where(binding, at, -1), at_ref.shape)
    count_ref[...] = jnp.broadcast_to(
        jnp.where(binding, k, over.astype(jnp.int32)), count_ref.shape)


@functools.partial(jax.jit, static_argnames=("topk", "interpret", "rows",
                                             "lanes"))
def select(
    scores: jnp.ndarray,      # [..., S] float32, NEG where a query sees none
    topk: int,
    span: jnp.ndarray | int | None = None,
    *,
    interpret: bool | pltpu.InterpretParams = False,
    rows: int | None = None,
    lanes: int | None = None,
):
    """The EXACT ``topk`` largest of ``scores`` a query, as the two numbers
    :func:`keep_mask` takes: (count [...] int32 of the keys it keeps, the
    k-th score and the k-th position [...]). A key is kept where its score
    is over the k-th, or equal to it at a position no later: ``lax.top_k``'s
    order among equals, zeros of both signs equal (as ``keep_mask`` compares
    them). A query that sees fewer than ``topk`` keys keeps them all: its
    k-th score reads :data:`NEG`, its k-th position -1. ``span`` (a traced
    scalar; None: ``S``): no query sees a key at or past it, and no round
    reads one. ``rows`` and ``lanes`` pick a program's block and a round's
    step in a test or a probe.

    **Nothing is sorted.** What a step needs of a row of 16 384 scores is a
    threshold, and ``lax.top_k`` at 2 048 of 16 384 SORTS the row: 0.42 ms
    for a decode step's 32 rows, 1.5-4.5 ms for a chunk's 512 queries, 12-18%
    of the glm cell's device time (PERF.md, PRs 58-59). Here a program holds
    ``rows`` rows in VMEM, read from HBM once, and finds a row's k-th score
    by bisection on the scores' bits: 32 rounds from the sign down, each a
    compare with a candidate and a count along the row, build the largest
    order key that ``topk`` scores reach; as many rounds as a position has
    bits then find where the last kept key of that score sits."""
    lead, width = scores.shape[:-1], scores.shape[-1]
    k = min(topk, width)
    flat = scores.reshape(-1, width)
    if width % 128:          # whole lane tiles: a test's width, no served one
        flat = jnp.pad(flat, ((0, 0), (0, -width % 128)),
                       constant_values=NEG)
    n, padded = flat.shape
    rows = rows or min(SELECT_ROWS, -(-n // 8) * 8)
    if lanes is None:        # whole lane tiles, a power of two of them
        lanes = 128
        while 2 * lanes * rows <= SELECT_STEP and padded % (2 * lanes) == 0:
            lanes *= 2
    span = width if span is None else span
    with jax.named_scope("dsa_topk"):
        out = pl.pallas_call(
            functools.partial(_select_kernel, k=k, lanes=lanes),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(pl.cdiv(n, rows),),
                in_specs=[pl.BlockSpec((rows, padded), lambda i, *_: (i, 0))],
                out_specs=[pl.BlockSpec((rows, 128), lambda i, *_: (i, 0))
                           for _ in range(3)]),
            out_shape=[jax.ShapeDtypeStruct((n, 128), dtype)
                       for dtype in (jnp.float32, jnp.int32, jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret, name="dsa_select",
        )(jnp.asarray(span, jnp.int32).reshape(1), flat)
    kth, at, count = (x[:, 0].reshape(lead) for x in out)
    return count, kth, at


def chosen_positions(keep: jnp.ndarray, topk: int) -> jnp.ndarray:
    """``[..., topk]`` int32: the positions :func:`keep_mask` kept, rising,
    -1 past their count. Made from the mask by ops nothing else reads, so a
    program that does not return it does not compute it: the served steps
    take the mask alone, the benchmark's judge reads the list. It is a sort
    of the row's positions, what the selection itself cost before it
    bisected: on the chip a prefix count and a scatter, or ``nonzero``, take
    nine and fourteen times a sort's 4.4 ms for 512 rows of 16 384 (PERF.md,
    PR 59)."""
    with jax.named_scope("dsa_chosen_positions"):
        pos = lax.broadcasted_iota(jnp.int32, keep.shape, keep.ndim - 1)
        width = keep.shape[-1]
        first, _ = lax.top_k(jnp.where(keep != 0, width - pos, 0),
                             min(topk, width))
        return jnp.where(first > 0, width - first, -1)


def keep_mask(scores: jnp.ndarray, kth_score: jnp.ndarray,
              kth_position: jnp.ndarray) -> jnp.ndarray:
    """``[.., S]`` int8: 1 at the keys :func:`select` chose of ``scores``
    (its k-th score and position a query), 0 elsewhere. A query that sees
    fewer than ``topk`` keys has :data:`NEG` for its k-th score and keeps
    every key it sees."""
    pos = lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    thr, at = kth_score[..., None], kth_position[..., None]
    kept = (scores > thr) | ((scores == thr) & (pos <= at))
    return (kept & (scores > NEG / 2)).astype(jnp.int8)
