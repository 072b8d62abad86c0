"""Pallas grouped matmul: the expert matmuls of a dropless mixture-of-experts
layer.

Rows arrive SORTED BY EXPERT (``models/llama.py:_moe_mlp`` sorts a step's
token-expert assignments), so expert ``e`` owns the contiguous rows
``offsets[e] .. offsets[e+1]`` and the layer is ``E`` matmuls of ragged
height. The kernel walks work items — one (row tile, expert) pair for every
tile an expert's rows touch, in row order — so the work is in the rows there
are and each touched expert's matrix is read once (an expert that spans two
tiles keeps its block resident between the two items). No expert has a
capacity and no row is dropped: an expert that every row chose is simply
every tile's item.

Like the paged kernels (``ops/paged_attention.py``) it takes the weights AS
THE TREE STACKS THEM, ``[L, E, K, N]``, and the layer as a scalar-prefetch
operand: a Mosaic call takes whole buffers, so a layer of the stack sliced in
front of it (what ``lax.scan`` over the layers hands its body, and what
``jax.lax.ragged_dot``'s own TPU kernel would be given) is a copy of every
expert of the layer, three times the bytes of the matmul itself. int8 weights
are converted block by block inside the kernel and their per-channel scale is
applied to the f32 result.

**The row tile follows from the call's shapes** (``row_tile(M, E)``, evaluated
when the call is traced, like ``ops/paged_attention.py: decode_trip_pages``):
64 rows where the mean group has at most 64 (``M <= 64 E``), ``ROW_TILE`` =
128 above. A work item multiplies a whole tile by its expert's block, whatever
rows of it are real, and converts an int8 block first: over sdar's 1.57 MB
blocks (1.92 us of bytes) an item takes 2.63 us at 128 rows and 2.26 / 2.17 /
2.05 at 64 / 32 / 16, but every tile boundary an expert's rows cross is one
more item that converts the block again, and under 64 rows those cost more
than the rows save (at 9 rows an expert one expert in eight crosses a
boundary of 64, one in two a boundary of 16). A converted block kept in a
VMEM scratch for an expert's further items costs its own store and load, 5-25%
of a call at every tile, so the kernel has none. Above 64 rows a group the
items that 128 saves win. The calls the benchmark's cells run (the kernel
alone on a v5e: ``PERF.md`` section 5):

===========================================  ======  ===  =========  ====
call (rows of the sorted assignments)        M       E    mean rows  tile
===========================================  ======  ===  =========  ====
sdar decode forward, 64 tokens top-8            512  128        4.0    64
granite decode step, 64 rows top-10             640   72        8.9    64
kimi decode step, the compacted rows            128   12       10.7    64
nemotron decode step, 64 rows top-22           1408  128       11.0    64
kimi 512-token mixed step, compacted            640   12       53.3    64
granite 512-token mixed step                   5760   72       80.0   128
nemotron 512-token mixed step                 12672  128       99.0   128
===========================================  ======  ===  =========  ====

A row's result does not depend on the tile: the same int8 block, the same
bfloat16 operands, one f32 accumulation over all of ``K``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the most rows of a tile (``row_tile`` picks the tile of a call), and the
#: unit of ``models/llama.py: moe_capacity``
ROW_TILE = 128
#: the most bytes of one expert's matrix a program takes as a block: a wider
#: matrix is walked in column tiles (the block is double-buffered and an
#: int8 block converted in VMEM, so 7168 x 2048 whole would not fit)
BLOCK_BYTES = 4 * 1024 * 1024


#: the rows of a tile where the mean group has at most this many rows
SMALL_ROW_TILE = 64


def row_tile(m: int, groups: int) -> int:
    """Rows of a tile of a call over ``m`` rows in ``groups`` groups, from
    those two shapes alone, evaluated when the call is traced:
    ``SMALL_ROW_TILE`` where the mean group has at most that many rows (``m
    <= 64 x groups``), ``ROW_TILE`` above, and one tile of its rows (whole
    sublane tiles of 16) for a call with fewer. The module docstring has the
    reasons and the cells' calls."""
    tm = SMALL_ROW_TILE if m <= SMALL_ROW_TILE * groups else ROW_TILE
    return min(tm, -(-m // 16) * 16)


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of a weight block: all ``n`` where ``k x n`` fits
    ``BLOCK_BYTES``, else the most whole lane tiles that divide ``n`` and
    fit."""
    if k * n * itemsize <= BLOCK_BYTES or n % 128:
        return n
    fits = [t for t in range(128, n, 128)
            if n % t == 0 and k * t * itemsize <= BLOCK_BYTES]
    return max(fits, default=128)


def _items_of(sizes: jnp.ndarray, tm: int):
    """Per group (``sizes`` int32) its first row, the row past its last, its
    first tile and the tiles its rows touch (0 for an empty group)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    return starts, ends, first, jnp.where(sizes > 0,
                                          (ends - 1) // tm - first + 1, 0)


def item_rows(group_sizes: jnp.ndarray, m: int) -> jnp.ndarray:
    """The rows ONE grouped matmul over ``m`` rows multiplies for these
    groups: its real work items times its tile (int32 scalar)."""
    tm = row_tile(m, group_sizes.shape[0])
    per = _items_of(group_sizes.astype(jnp.int32), tm)[3]
    return jnp.sum(per).astype(jnp.int32) * tm


def group_items(group_sizes: jnp.ndarray, m_pad: int, tm: int):
    """The work items of one grouped matmul over ``m_pad`` rows in tiles of
    ``tm``: per item its row tile, its expert and the expert's row range,
    ``n_items`` long (static: every tile once, plus one for every further
    expert that can start inside a tile), and how many of them are real. The
    rest repeat the last real item, so their blocks are resident and their
    body is skipped."""
    E = group_sizes.shape[0]
    tiles = m_pad // tm
    n_items = tiles + min(E, m_pad) - 1
    starts, ends, first, per = _items_of(group_sizes.astype(jnp.int32), tm)
    item_ends = jnp.cumsum(per)
    n_real = item_ends[-1]
    t = jnp.minimum(jnp.arange(n_items, dtype=jnp.int32),
                    jnp.maximum(n_real - 1, 0))
    expert = jnp.minimum(
        jnp.searchsorted(item_ends, t, side="right").astype(jnp.int32), E - 1)
    tile = first[expert] + t - (item_ends[expert] - per[expert])
    return (tile.astype(jnp.int32), expert, starts[expert], ends[expert],
            n_real.reshape(1))


def _kernel(tile_ref, expert_ref, lo_ref, hi_ref, n_ref, layer_ref, x_ref,
            w_ref, *rest, tm: int, scaled: bool):
    """One work item of one column tile (grid: column tiles x items): rows
    ``lo..hi`` of tile ``tile`` times that column tile of expert ``expert``'s
    matrix, added into the tile's output block (stored on the tile's first
    item, so a block is never read before it is written)."""
    s_ref, o_ref = rest if scaled else (None, rest[0])
    t = pl.program_id(1)

    @pl.when(t < n_ref[0])
    def _item():
        tile = tile_ref[t]
        rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (rows >= lo_ref[t]) & (rows < hi_ref[t])
        w = w_ref[0, 0]
        if w.dtype != x_ref.dtype:
            # int8 -> f32 -> the activations' dtype: the two-step convert is
            # the one Mosaic lowers for every source width (every item
            # converts its block again: the module docstring says why)
            w = w.astype(jnp.float32).astype(x_ref.dtype)
        y = jax.lax.dot_general(x_ref[...], w, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scaled:
            y = y * s_ref[0, 0]
        y = jnp.where(mine, y, 0.0)
        opens = (t == 0) | (tile_ref[jnp.maximum(t - 1, 0)] != tile)

        @pl.when(opens)
        def _store():
            o_ref[...] = y

        @pl.when(jnp.logical_not(opens))
        def _add():
            o_ref[...] += y


@functools.partial(jax.jit, static_argnames=("interpret", "tile_rows"))
def grouped_matmul(
    x: jnp.ndarray,            # [M, K] rows sorted by group
    w: jnp.ndarray,            # [L, E, K, N] the stacked expert matrices
    scale: jnp.ndarray | None,  # [L, E, N] f32 per-channel scale, or None
    group_sizes: jnp.ndarray,  # [E] int32 rows of each group, summing to M
    layer: jnp.ndarray | int = 0,
    interpret: bool = False,
    tile_rows: int | None = None,
) -> jnp.ndarray:
    """``out[r] = x[r] @ w[layer, g(r)] * scale[layer, g(r)]`` in f32, where
    ``g(r)`` is the group whose row range holds ``r``. ``tile_rows`` (a
    multiple of 16) takes the place of :func:`row_tile`'s pick, for the tests
    and the probe: a row's result does not depend on it."""
    M, K = x.shape
    L, E, _, N = w.shape
    tm = tile_rows or row_tile(M, E)
    m_pad = -(-M // tm) * tm
    if m_pad != M:
        x = jnp.pad(x, ((0, m_pad - M), (0, 0)))
    tile, expert, lo, hi, n_real = group_items(group_sizes, m_pad, tm)
    scaled = scale is not None
    # the column tiles (one, where the matrix fits a block) are the OUTER
    # grid axis, so that a tile's items stay consecutive and an output block
    # is finished before the next
    tn = _col_tile(K, N, w.dtype.itemsize)

    def rows_at(j, t, tile, *_):
        return (tile[t], 0)

    def out_at(j, t, tile, *_):
        return (tile[t], j)

    def expert_at(j, t, tile, expert, lo, hi, n, layer):
        return (layer[0], expert[t], 0, j)

    in_specs = [pl.BlockSpec((tm, K), rows_at),
                pl.BlockSpec((1, 1, K, tn), expert_at)]
    operands = [x, w]
    if scaled:
        in_specs.append(pl.BlockSpec((1, 1, 1, tn), expert_at))
        operands.append(scale.reshape(L, E, 1, N).astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(N // tn, tile.shape[0]),
            in_specs=in_specs, out_specs=pl.BlockSpec((tm, tn), out_at)),
        out_shape=jax.ShapeDtypeStruct((m_pad, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(tile, expert, lo, hi, n_real,
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out[:M]
