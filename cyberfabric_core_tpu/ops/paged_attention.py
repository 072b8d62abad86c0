"""Pallas paged-attention kernels over a paged KV pool: decode (T=1), and
the ragged mixed-batch kernel of a prompt's chunks.

The decode hot loop reads each sequence's KV history through a page table
instead of a dense per-slot cache. Neither kernel has a grid over the table:
a program walks the pages its queries read itself, through
``ops/page_walk.py``'s ``_Walk`` (the latent kernels' walk, here over K and V
pages that share a page id, a ring each). The pools are left where they
live; the page table, the lengths and the layer ride in as scalar prefetch;
a **trip** of consecutive pages of a span lands in one key block of each
ring and is attended over as ONE block (:func:`_attend_block`): one mask by
position, and a kv head at a time one score dot, one online-softmax update
and one value dot over THAT head's query rows, which the wrappers hand over
as one slab a kv head. No program and no copy exists for a slot of the table
outside a span, and the rings run on across the programs of a call, so a
row's last trips are in flight with the next row's first.

The decode kernel (``paged_decode_attention``; since PR 57, ROADMAP S17,
closed: a grid program a group of four pages before): a program is a ROW,
``grid=(B,)``; its span is :func:`page_span` on scalars in the program (up to
the page of its own token, from the page that holds the window's first key;
a row at length 0 has no trips and writes zeros); a trip is
:func:`decode_trip_pages` pages (16 of 64 tokens at 8 kv heads of 128; the 9
a window of 512 spans, so a window layer's row is ONE trip; what
``llm_attn_page_groups_total`` counts).

The ragged kernel (``ragged_paged_attention``: the lanes of a mixed step;
since PR 55, ROADMAP S18, closed): a program is a (lane, block of 64
queries); the spans are worked out once a call and ride in as scalar
prefetch; a trip is up to 16 pages, one key block a kv head over its ``G x
64`` query rows.

Why this beats the dense path (VERDICT r1 weak #3/#6): attention reads scale
with the *tokens actually present* (sum of per-slot lengths), not
n_slots × max_seq — idle slots cost one scratch-page read, and short sequences
don't drag the whole window through HBM every step. Pages are shared
cross-request (prefix cache) with zero copies: sharing is rows in the page
table, exactly the PAPERS.md "ragged paged attention for TPU" direction.

Both kernels take the STACKED pool as the pool keeps it — ``[L, N, page,
Hkv*D]``, head-major on the merged minor axis (runtime/paged.py) — and the
layer as one more scalar-prefetch operand: a DMA is a page ``(page, Hkv*D)``
at ``(layer, page_table[b, jj])``, so nothing pool-sized is sliced, reshaped
or copied in front of the call. (On a tiled TPU layout a merge of
the two minor dimensions is a physical copy, and a Mosaic call takes whole
buffers, so ``pool[layer]`` materialises: PERF.md section 6, PR 25.)

The reference has no decode path at all (inference is delegated to external
providers — SURVEY §0); this kernel is TPU-first substrate for the
llm-gateway local worker (BASELINE config #2: 64 concurrent streams).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import page_walk
from .page_walk import (_LANES, _VMEM_LIMIT, _Walk, _online_softmax_step,
                        _walk_scratch, page_span, ragged_span)


def _whole_lane_tiles(kv_lanes: int, interpret) -> None:
    """A walking kernel copies a page as the pool holds it, and Mosaic takes
    a DMA only of whole lane tiles: every served model's ``Hkv * D`` (a
    shard's under ``tp``) is a multiple of 128; a toy's 32 runs in interpret
    mode alone."""
    if not interpret and kv_lanes % _LANES:
        raise ValueError(
            f"a pool row of {kv_lanes} numbers is not whole lane tiles of "
            f"{_LANES}: the paged kernels cannot copy its pages on the chip")


def _attend_block(q_ref, q_at: tuple, k_ring, v_ring, slot, k_start, visible,
                  acc_ref, m_ref, l_ref, *, n_keys: int, scale: float | None,
                  two_d_dots: bool, first: bool, head_loop: bool):
    """One trip of a walk over K and V pages: the first ``n_keys`` rows of
    key block ``slot`` of the two rings are ONE block of keys whose first
    sits at position ``k_start``, attended over by ``q_ref[q_at]`` [Hkv, GQ,
    D] (indexed as it is loaded: a view of a block whose heads are no whole
    lane tiles does not lower), a kv head's query rows one slab (the decode kernel's ``G`` rows padded to
    whole sublane tiles; the ragged kernel's ``G x Qb``), over that head's
    slab of the accumulators ``acc_ref`` [Hkv * GQ, D], ``m_ref`` / ``l_ref``
    [Hkv * GQ, LANES]. ``visible``: the mask of a block of key positions
    [rows, keys], the one thing the two kernels differ in (a decode row is
    one position, a q-block one a query). ``first``: the first trip of its
    walk, which reads nothing from the accumulators.

    A kv head's score dot is ``[GQ, D] x [keys, D]`` over ITS query rows and
    its value dot f32 ``p`` against ``v`` cast up. ``two_d_dots`` (the
    Mosaic-lowerable form: its dot supports only 2D operands) takes a head's
    keys and values as REF-level lane slices of the minor-merged ring block
    (value-level bf16 lane slices at non-zero tile offsets are an
    unlowerable relayout, ref-level sliced LOADS are not); the batched form
    is one dot over every kv head, which Mosaic cannot lower and interpret
    mode keeps for tier-1 wall-clock. Bitwise identical: each output element
    is the same contraction.

    ``head_loop`` says how the 2D form goes through the heads. True (the
    ragged kernel: a head's ``G x Qb`` rows are hundreds, every head's
    scores at once would be megabytes): a ``fori_loop`` whose body is one
    head's score dot, online-softmax update on its slab and value dot,
    traced once. False (the decode kernel: every head's rows are 64-128):
    the heads' score dots written out one after another, ONE update over
    all their rows, then the heads' value dots. In a loop a head's update
    (vector unit) waits for its own score dot and its value dot (matrix
    unit) for the update, head after head; written out, the dots of one
    kind are independent and follow each other through the matrix unit. The
    decode kernel's arithmetic alone read 517 -> 214 us a call at laguna's
    full layers, 206 -> 76 at its window layers, 53 -> 22 at ouro's shape
    (PERF.md section 5, PR 57)."""
    Hkv, GQ, D = q_ref.shape[len(q_at):]
    keys = pl.ds(0, n_keys)
    sm_scale = 1.0 / (D ** 0.5) if scale is None else scale
    rows = GQ if two_d_dots and head_loop else Hkv * GQ
    mask = visible(k_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, n_keys), 1))
    nt, nn = (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ()))
    if not two_d_dots:
        k = k_ring[slot, keys].reshape(n_keys, Hkv, D)
        scores = jax.lax.dot_general(
            q_ref[q_at], jnp.transpose(k, (1, 2, 0)),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)         # [Hkv, GQ, keys]

        def weigh(p):
            v = v_ring[slot, keys].reshape(n_keys, Hkv, D)
            pg = p.reshape(Hkv, GQ, n_keys)
            return jax.lax.dot_general(
                pg, jnp.transpose(v, (1, 0, 2)).astype(pg.dtype),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(rows, D)

        _online_softmax_step(scores.reshape(rows, n_keys) * sm_scale, mask,
                             weigh, acc_ref, m_ref, l_ref, first)
        return

    if not head_loop:
        def lanes(kv):
            return pl.ds(kv * D, D)

        scores = jnp.concatenate([jax.lax.dot_general(
            q_ref[(*q_at, kv)], k_ring[slot, keys, lanes(kv)], nt,
            preferred_element_type=jnp.float32) for kv in range(Hkv)], axis=0)
        _online_softmax_step(
            scores * sm_scale, mask,
            lambda p: jnp.concatenate([jax.lax.dot_general(
                p[kv * GQ:(kv + 1) * GQ],
                v_ring[slot, keys, lanes(kv)].astype(p.dtype), nn,
                preferred_element_type=jnp.float32)
                for kv in range(Hkv)], axis=0),
            acc_ref, m_ref, l_ref, first)
        return

    def one_head(kv, _):
        if isinstance(kv, int):
            head, slab = pl.ds(kv * D, D), pl.ds(kv * GQ, GQ)
        else:
            head = pl.ds(pl.multiple_of(lax.mul(kv, D), _LANES), D)
            slab = pl.ds(pl.multiple_of(lax.mul(kv, GQ), 8), GQ)
        scores = jax.lax.dot_general(
            q_ref[(*q_at, kv)], k_ring[slot, keys, head], nt,
            preferred_element_type=jnp.float32)         # [GQ, keys]
        _online_softmax_step(
            scores * sm_scale, mask,
            lambda p: jax.lax.dot_general(
                p, v_ring[slot, keys, head].astype(p.dtype), nn,
                preferred_element_type=jnp.float32),
            acc_ref.at[slab], m_ref.at[slab], l_ref.at[slab], first)

    if D % _LANES:
        # a head's lanes at an offset that is no whole lane tile: only a
        # static slice lowers (phi-3's head of 96)
        for kv in range(Hkv):
            one_head(kv, None)
    else:
        lax.fori_loop(0, Hkv, one_head, None)


#: K bytes (and as many of V) a key block of the decode kernel's rings holds
#: at most: 16 pages of 64 tokens at 8 kv heads of 128 in bfloat16, 12 MB of
#: rings, what the ragged kernel holds; ouro's 16 kv heads take 8 pages
_TRIP_BYTES = 2 << 20


def decode_trip_pages(page_size: int, kv_lanes: int, itemsize: int,
                      n_pages: int, sliding_window: int | None) -> int:
    """Pages a trip of the decode kernel takes, from shapes
    (``page_walk.decode_trip_pages``: ``TRIP_PAGES``, and no more than the
    window spans, so that a window layer's row is ONE trip that reads no
    accumulator), no more than a row of the table has and no more than
    ``_TRIP_BYTES`` of K a key block (``kv_lanes`` is ``Hkv * D``, a pool
    row's numbers: a shard's own under ``tp``). What the scheduler counts a
    row's trips by (``llm_attn_page_groups_total``)."""
    return page_walk.decode_trip_pages(
        page_size, sliding_window,
        min(n_pages, max(1, _TRIP_BYTES // (page_size * kv_lanes * itemsize))))


def _decode_kernel(pt_ref, len_ref, layer_ref, q_ref, k_pool_ref, v_pool_ref,
                   o_ref, k_ring, v_ring, sem, walk_ref, acc_ref, m_ref,
                   l_ref, *, page_size: int, trip: int,
                   sliding_window: int | None, two_d_dots: bool,
                   scale: float | None):
    """One slot: the program walks its row's span itself (``ops/page_walk.py``:
    :class:`_Walk`, an item a row; K and V of a page are copied together,
    each into its own ring).

    Refs:
      pt_ref: [B, Pmax] int32 SMEM — the page table
      len_ref: [B] int32 SMEM — valid kv length per slot (incl. current token)
      layer_ref: [1] int32 SMEM
      q_ref:   [1, Hkv, Gp, D] VMEM — a kv head's ``G`` query rows one slab,
        padded to whole sublane tiles (the wrapper's layout)
      k_pool_ref, v_pool_ref: the whole stacked pools, where they live
      o_ref:   [1, Hkv, Gp, D] VMEM
      k_ring, v_ring, sem, walk_ref, acc_ref, m_ref, l_ref:
        :func:`_walk_scratch` of two pools at ``Hkv * Gp`` rows

    A trip's pages are ONE key block (:func:`_attend_block`): one score dot,
    one online-softmax update and one value dot a kv head over ITS query
    rows, under one mask by position. A row at length 0 has no trips and
    writes zeros."""
    b = pl.program_id(0)
    n_rows, n_pages = pt_ref.shape
    walk = _Walk(pt_ref, layer_ref, (k_pool_ref, v_pool_ref),
                 (k_ring, v_ring), sem, walk_ref, n_items=n_rows, trip=trip,
                 sizes=kv_block_sizes(trip), page_size=page_size,
                 idle=lambda row: len_ref[row] == 0,
                 span=lambda row: page_span(len_ref[row], page_size, n_pages,
                                            sliding_window),
                 row=lambda row: row, unroll=False)

    @pl.when(b == 0)
    def _open():
        walk.open(fill=(v_ring,))

    length = len_ref[b]

    @pl.when(length == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    # the slot's own tokens, and nothing past the table (a length past it)
    bound = lax.min(length, n_pages * page_size)

    def visible(k_pos):
        mask = k_pos < bound
        if sliding_window is not None:      # the query sits at length - 1
            mask &= k_pos >= length - sliding_window
        return mask

    @pl.when(length > 0)
    def _busy():
        def attend(slot, k_start, *, pages, first):
            _attend_block(q_ref, (0,), k_ring, v_ring, slot, k_start,
                          visible, acc_ref, m_ref, l_ref,
                          n_keys=pages * page_size, scale=scale,
                          two_d_dots=two_d_dots, first=first,
                          head_loop=False)

        walk.run(b, attend)
        denom = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).reshape(
            o_ref.shape[1:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "interpret", "sliding_window", "two_d_dots", "scale", "name", "trip"))
def paged_decode_attention(
    q: jnp.ndarray,           # [B, Hq, D] — one query token per slot
    k_pool: jnp.ndarray,      # [L, N, page, Hkv*D] — the stacked page pool
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Pmax] int32 physical page ids
    lengths: jnp.ndarray,     # [B] int32 valid length (incl. current token)
    layer: jnp.ndarray | int = 0,  # scalar int32 — which layer's pages
    *,
    interpret: bool | pltpu.InterpretParams = False,
    sliding_window: int | None = None,
    two_d_dots: bool | None = None,
    scale: float | None = None,
    name: str | None = None,      # the call site's, in a device trace
    trip: int | None = None,      # a test's or a probe's pages a trip
) -> jnp.ndarray:
    """Returns [B, Hq, D] attention over each slot's paged history in layer
    ``layer`` of the pool. One program a slot, in order; the pools stay
    where they live and the programs copy the pages of a row's span
    (:func:`page_span`: up to the page of its own token, from the page that
    holds the window's first key) themselves, K and V of a page together,
    :func:`decode_trip_pages` at a time. ``q`` goes in with a kv head's
    ``G`` query rows one slab, padded to whole sublane tiles, and the output
    comes back the same way (request-sized reshapes, here). At ``trip=1``
    the sums run in the order of the grid this kernel had up to PR 56 at a
    page a program and give its bytes; at the shipped trip it is another
    order of the same sums.

    ``scale`` is the softmax scale where the model gives one (absent:
    ``D^-1/2``); ``name`` is what a device trace calls this call site's
    kernel (absent: the kernel's own name), for a model that calls it for
    two kinds of layer.

    ``two_d_dots`` (default: on exactly when compiling for real) runs the kv
    heads' 2D dots one after another over ref-level lane slices, where the
    batched form is one dot over every kv head, which Mosaic cannot lower;
    bitwise-identical (golden-pinned)."""
    if two_d_dots is None:
        two_d_dots = not interpret
    B, Hq, D = q.shape
    _, _, page_size, HD = k_pool.shape
    _whole_lane_tiles(HD, interpret)
    Hkv = HD // D
    G = Hq // Hkv
    Gp = -(-G // 8) * 8
    trip = trip or decode_trip_pages(page_size, HD, k_pool.dtype.itemsize,
                                     page_table.shape[1], sliding_window)
    slabs = (B, Hkv, Gp, D)
    q = q.reshape(B, Hkv, G, D)
    if Gp != G:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    def at_row(i, *_):
        return (i, 0, 0, 0)

    block_spec = pl.BlockSpec((1, *slabs[1:]), at_row)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, trip=trip,
                          sliding_window=sliding_window,
                          two_d_dots=two_d_dots, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[block_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block_spec,
            scratch_shapes=_walk_scratch(trip, page_size, HD, k_pool.dtype,
                                         Hkv * Gp, D, pools=2)),
        out_shape=jax.ShapeDtypeStruct(slabs, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool, v_pool)
    return out[:, :, :G].reshape(B, Hq, D)


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


def kv_block_sizes(trip: int) -> tuple[int, ...]:
    """The key blocks a trip of either K/V kernel is attended over as, in
    pages: the trip, and 4 pages (256 keys) for what a short row or lane, a
    q-block with little behind it or a span's last trip holds. Two and not
    the latent kernels' four (2 / 4 / 8 / 16): each size is two traced bodies
    of the attend in every program a kernel sits in, and with four the
    16-row cells paid for them at every start (qwen2 ``jax_trace_lower_s``
    11.7 -> 13.4 s on a warm traced pair; PERF.md, PR 55); the decode
    kernel reads the same within 1-6% at one, two or four (PR 57)."""
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

    A trip's pages are ONE key block (:func:`_attend_block`): one score dot,
    one online-softmax update and one value dot a kv head, over its slab of
    the accumulators, under one mask by query position.

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
                 sizes=kv_block_sizes(trip), page_size=page_size,
                 idle=lambda item: last_ref[item] < first_ref[item],
                 span=lambda item: (first_ref[item], last_ref[item]),
                 row=lambda item: lax.div(item, q_blocks), unroll=False)

    @pl.when(item == 0)
    def _open():
        walk.open(fill=(v_ring,))

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
        _attend_block(q_ref, (0, 0), k_ring, v_ring, slot, k_start, visible,
                      acc_ref, m_ref, l_ref, n_keys=pages * page_size,
                      scale=scale, two_d_dots=two_d_dots, first=first,
                      head_loop=True)

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
    _whole_lane_tiles(HD, interpret)
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


def paged_block_attention(q, k_pool, v_pool, page_table, lengths, layer=0,
                          interpret: bool = False, trip: int | None = None):
    """The open block of a model that generates by diffusion over blocks:
    ``q`` [B, W, Hq, D], the block's W queries a row, every one of which sees
    all of ``lengths`` keys (the row's kept history and the block itself,
    which the caller has written). That is :func:`paged_decode_attention`
    with the block folded into the GQA group axis, W x G query rows a kv
    head's slab, so the pages are walked once a row and not once a position.
    No sliding window. Returns [B, W, Hq, D]."""
    B, W, Hq, D = q.shape
    Hkv = k_pool.shape[3] // D
    G = Hq // Hkv
    folded = q.reshape(B, W, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    out = paged_decode_attention(
        folded.reshape(B, Hkv * W * G, D), k_pool, v_pool, page_table,
        lengths, layer, interpret=interpret, trip=trip)
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
