"""Pallas TPU kernel: batched sketch ingest, grouped by counter tile.

The paper's per-edge scatter ``M[h(x), h(y)] += w`` is re-expressed per
(counter tile × chunk of the tile's entries) as

    M_tile += OneHot_rows(chunk) @ (OneHot_cols(chunk) * w)^T

— a (TR × CB) @ (CB × TC) systolic matmul with fp32 accumulation in VMEM.
Each edge lands in exactly one tile per depth, so each tile contracts only
the entries that hash into it: the work follows the batch, not the batch
times the sketch.

Grouping (:func:`group_metadata`, traced into the caller's jit): per depth,
each entry is keyed by its tile id ``(r // TR) * (wc / TC) + c // TC``; row
-1 entries (padding, out-of-shard rows) take the sentinel key ``T`` past the
last tile, with weight 0.  One batched ``lax.sort`` over (d, B) orders
(key, r, c, w) along B.  Each tile's run starts where the sorted keys first
reach its id, found by counting keys below it: first among the chunk heads,
then inside one chunk.  The runs become a ragged work list, megablox style:
one item per (tile, CB-chunk of the sorted array that overlaps the tile's
run).  Consecutive runs share at most their boundary chunk, and a batch of
B entries touches at most B tiles, so a depth has at most
``B/CB + min(T, B) - 1`` items.

Grid = (d, B/CB + min(T, B) - 1), that static bound (:func:`grid_steps`).
The item tables ride in SMEM through ``PrefetchScalarGridSpec`` and steer
the block index maps: item i's counter tile is DMA'd in when it differs from
item i-1's and written back when item i+1's differs, so a touched tile is
read and written once.  Items past a depth's real count repeat its last
item, so they move no data, and skip compute under ``pl.when``.  Tiles no
item visits are never read or written: ``input_output_aliases`` keeps them
in place.  Entries of another tile that share a straddling chunk fail this
tile's iota compare and add exactly zero; row -1 entries match no row.

Exactness: each weight is split into three bfloat16 parts that sum to it
exactly (8 significant bits each, 24 in all: a float32 significand), and
the one-hot rows meet each part in one bf16 matmul with fp32 accumulation.
A product with a one-hot is exact, so no weight is rounded; this costs half
the MXU passes of ``Precision.HIGHEST`` on the same operands.  (A weight so
small that its low part falls below bf16's subnormal range, |w| < 2**-100,
loses that part, as TPU arithmetic flushes such values anyway.)

VMEM working set per program:
    2 * 2 * TR*TC*4 (counter tile in and out, double-buffered)
    + CB*(TR+TC)*(4+2) (one-hots, fp32 and bf16) + TR*TC*4 (the update)
    + 3 * 2 * CB*4 (entries)
    = 2 MB + 576 KB + 512 KB + 3 KB ≈ 3.1 MB  « 16 MB scoped VMEM.
MXU alignment: TR, TC and CB multiples of 128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

TILE_R = 256
TILE_C = 512
CHUNK_B = 128


def _cdiv(a, b):
    return -(-a // b)


def _steps_per_depth(wr: int, wc: int, b: int) -> int:
    tiles = _cdiv(wr, TILE_R) * _cdiv(wc, TILE_C)
    return _cdiv(b, CHUNK_B) + min(tiles, b) - 1


def grid_steps(d: int, wr: int, wc: int, b: int) -> int:
    """Grid length of one ingest of ``b`` entries into (d, wr, wc) counters,
    shapes before padding (the static bound of the module docstring)."""
    return d * _steps_per_depth(wr, wc, _cdiv(b, CHUNK_B) * CHUNK_B)


def _count_below(keys, q):
    """``sum(keys < q)`` for each query, ``keys`` sorted (B,), B % CB == 0:
    the chunk heads locate the chunk holding the boundary, and one compare
    across that chunk's row finishes the count."""
    rows = keys.reshape(-1, CHUNK_B)
    j = jnp.sum(rows[None, :, 0] < q[:, None], axis=1) - 1
    inside = jnp.sum(rows[jnp.maximum(j, 0)] < q[:, None], axis=1)
    return jnp.where(j < 0, 0, j * CHUNK_B + inside).astype(jnp.int32)


def group_metadata(rows, cols, weights, wr: int, wc: int):
    """Sort one batch by counter tile and build the kernel's work list.

    rows/cols (d, B) int32 (row -1 inert), weights (B,) f32, for counters
    (d, wr, wc) with wr % TILE_R == wc % TILE_C == B % CHUNK_B == 0.
    Returns ``(rows, cols, weights)`` sorted per depth, each (d, B), and
    ``(item_tile, item_chunk, n_items)``: the tile and chunk of every grid
    step, flattened (d * steps,), and the occupied steps per depth (d,)."""
    b = rows.shape[1]
    n_tc = wc // TILE_C
    tiles = (wr // TILE_R) * n_tc
    steps = _steps_per_depth(wr, wc, b)
    live = rows >= 0
    key = jnp.where(live, (rows // TILE_R) * n_tc + cols // TILE_C, tiles)
    w = jnp.where(live, weights[None, :], 0.0)
    key, rows, cols, w = jax.lax.sort((key, rows, cols, w), dimension=1, num_keys=1)
    ids = jnp.arange(tiles + 1, dtype=jnp.int32)
    bounds = jax.vmap(_count_below, in_axes=(0, None))(key, ids)   # (d, T+1)
    start, end = bounds[:, :-1], bounds[:, 1:]
    first_chunk = start // CHUNK_B
    n = jnp.where(end > start, _cdiv(end, CHUNK_B) - first_chunk, 0)  # (d, T)
    cum = jnp.cumsum(n, axis=1)
    n_items = cum[:, -1]
    # Steps past the real count repeat the last item (same blocks, no DMA).
    last = jnp.maximum(n_items - 1, 0)[:, None]
    i = jnp.minimum(jnp.arange(steps, dtype=jnp.int32)[None, :], last)
    tile = jax.vmap(
        functools.partial(jnp.searchsorted, side="right", method="compare_all")
    )(cum, i)
    tile = jnp.minimum(tile, tiles - 1).astype(jnp.int32)        # (d, steps)
    at_tile = functools.partial(jnp.take_along_axis, indices=tile, axis=1)
    chunk = at_tile(first_chunk) + i - (at_tile(cum) - at_tile(n))
    return (rows, cols, w), (tile.reshape(-1), chunk.reshape(-1), n_items)


def _bf16_parts(w):
    """Three bf16 arrays whose fp32 sum is exactly ``w`` (f32)."""
    hi = w.astype(jnp.bfloat16)
    rest = w - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _ingest_kernel(
    tile_ref, chunk_ref, count_ref, rows_ref, cols_ref, w_ref, counters_ref, out_ref,
    *, steps: int, n_tc: int,
):
    """One work item: chunk ``chunk_ref[s]`` against tile ``tile_ref[s]``."""
    g = pl.program_id(0)
    i = pl.program_id(1)
    s = g * steps + i
    tile = tile_ref[s]

    @pl.when((i == 0) | (tile != tile_ref[jnp.maximum(s - 1, 0)]))
    def _init():
        out_ref[...] = counters_ref[...]

    @pl.when(i < count_ref[g])
    def _accumulate():
        rows = rows_ref[0]                      # (1, CB) int32, global row ids
        cols = cols_ref[0]
        r0 = (tile // n_tc) * TILE_R
        c0 = (tile % n_tc) * TILE_C
        # one-hot via iota compare; ids of other tiles match no row or col
        iota_r = jax.lax.broadcasted_iota(jnp.int32, (TILE_R, CHUNK_B), 0)
        iota_c = jax.lax.broadcasted_iota(jnp.int32, (TILE_C, CHUNK_B), 0)
        oh_r = (iota_r == rows - r0).astype(jnp.float32).astype(jnp.bfloat16)
        oh_c = (iota_c == cols - c0).astype(jnp.float32)         # (TC, CB)
        upd = None
        for part in _bf16_parts(w_ref[0]):      # (1, CB) each
            # one-hot × bf16 part is bf16-exact, so the casts round nothing
            term = jax.lax.dot_general(
                oh_r,
                (oh_c * part.astype(jnp.float32)).astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (TR, TC)
            upd = term if upd is None else upd + term
        out_ref[...] += upd[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ingest_pallas(
    counters, rows, cols, weights, interpret: Optional[bool] = None
):
    """counters (d, wr, wc) f32; rows/cols (d, B) int32, row -1 inert;
    weights (B,) f32.  Shapes must be pre-padded: wr % TILE_R == wc % TILE_C
    == B % CHUNK_B == 0 (``repro.core.ingest`` handles padding)."""
    d, wr, wc = counters.shape
    n_tc = wc // TILE_C
    steps = _steps_per_depth(wr, wc, rows.shape[1])
    (rows, cols, w), (item_tile, item_chunk, n_items) = group_metadata(
        rows, cols, weights, wr, wc
    )

    def entries(g, i, tile_ref, chunk_ref, count_ref):
        return (g, 0, chunk_ref[g * steps + i])

    def tile_block(g, i, tile_ref, chunk_ref, count_ref):
        t = tile_ref[g * steps + i]
        return (g, t // n_tc, t % n_tc)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d, steps),
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK_B), entries),   # rows
            pl.BlockSpec((1, 1, CHUNK_B), entries),   # cols
            pl.BlockSpec((1, 1, CHUNK_B), entries),   # weights
            pl.BlockSpec((1, TILE_R, TILE_C), tile_block),
        ],
        out_specs=pl.BlockSpec((1, TILE_R, TILE_C), tile_block),
    )
    return pl.pallas_call(
        functools.partial(_ingest_kernel, steps=steps, n_tc=n_tc),
        grid_spec=grid_spec,
        # Inside shard_map the updated counters vary over the same mesh axes
        # as the counters they alias.
        out_shape=jax.ShapeDtypeStruct(
            counters.shape, jnp.float32, vma=jax.typeof(counters).vma
        ),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=resolve_interpret(interpret),
    )(
        item_tile, item_chunk, n_items.astype(jnp.int32),
        rows[:, None, :], cols[:, None, :], w[:, None, :], counters,
    )
