"""Attention over a chosen set: the index pass, the exact top-k, and the mask
by which the chosen keys reach the latent attention kernels.

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

**The top-k is exact** (:func:`select`, ``jax.lax.top_k``: ties go to the
lower position): an approximate set would be another result.

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


def _prefixes(keys: int, topk: int, most: int = 3) -> tuple[int, ...]:
    """The prefixes of a row of ``keys`` scores a top-k may be taken over,
    rising: ``keys`` halved while a prefix still holds ``2 topk``, at most
    ``most`` of them. A sort's cost is its width's (``lax.top_k`` at 2 048 of
    16 384 sorts the row: 4.5 ms for 512 queries), whatever lies in it."""
    sizes = [keys]
    while len(sizes) < most and sizes[-1] % 2 == 0 \
            and sizes[-1] // 2 >= 2 * topk:
        sizes.append(sizes[-1] // 2)
    return tuple(reversed(sizes))


def select(scores: jnp.ndarray, topk: int, span=None):
    """The EXACT ``topk`` largest of ``scores`` [..., S] a query:
    (positions [..., topk] int32 by falling score, -1 past the keys the
    query sees, count [...] int32 of those it keeps, the k-th score and the
    k-th position [...]: a key is kept where its score is over the k-th, or
    equal to it at a position no later, which is ``lax.top_k``'s order among
    equals). ``topk`` over ``S``: every key the query sees. ``span`` (a
    traced scalar; None: ``S``): no query sees a key at or past it, so the
    top-k is taken over the shortest of :func:`_prefixes` that holds it:
    the same set, a narrower sort."""
    with jax.named_scope("dsa_topk"):
        k = min(topk, scores.shape[-1])

        def over(width: int, scores):
            values, positions = lax.top_k(scores[..., :width], k)
            seen = values > NEG / 2
            positions = jnp.where(seen, positions, -1).astype(jnp.int32)
            return (positions, jnp.sum(seen, axis=-1, dtype=jnp.int32),
                    values[..., -1], positions[..., -1])

        sizes = _prefixes(scores.shape[-1], k)
        if span is None or len(sizes) == 1:
            return over(sizes[-1], scores)
        at = sum((span > width).astype(jnp.int32) for width in sizes[:-1])
        return lax.switch(at, [functools.partial(over, width)
                               for width in sizes], scores)


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
