"""Pallas TPU kernel: batched sketch ingest as one-hot MXU matmuls.

The paper's per-edge scatter ``M[h(x), h(y)] += w`` is re-expressed per
(row-tile × col-tile × edge-chunk) as

    M_tile += OneHot_rows(chunk) @ (OneHot_cols(chunk) * w)^T

— a (TR × CB) @ (CB × TC) systolic matmul with fp32 accumulation in VMEM.
The one-hots are built transposed, (tile × chunk), so the chunk's ids stay
on the lane axis as they arrive: indices are laid out (d, 1, B) and weights
(1, B), whose (1, CB) blocks meet the TPU tiling rule (last two block dims
divisible by (8, 128) or equal to the array's).  The contraction runs at
``Precision.HIGHEST``: weighted one-hots are not bf16-exact, and the
counting regime's bit-exactness needs full fp32 products.
Grid = (d, wr/TR, wc/TC, B/CB); the edge-chunk axis is innermost so each
counter tile stays resident in VMEM while every chunk accumulates into it
(input_output_aliasing keeps the update in place).

VMEM working set per program:
    TR*TC*4 (tile) + 2*CB*4 (indices) + CB*4 (weights) + CB*(TR+TC)*4
    = 256*256*4 + ... ≈ 1.3 MB  « 16 MB VMEM.
MXU alignment: TR, TC and CB multiples of 128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE_R = 256
TILE_C = 256
CHUNK_B = 512


def _ingest_kernel(rows_ref, cols_ref, w_ref, counters_ref, out_ref):
    """One (d, r-tile, c-tile, b-chunk) program."""
    i_r = pl.program_id(1)
    i_c = pl.program_id(2)
    i_b = pl.program_id(3)

    @pl.when(i_b == 0)
    def _init():
        out_ref[...] = counters_ref[...]

    rows = rows_ref[0]                          # (1, CB) int32, global row ids
    cols = cols_ref[0]
    w = w_ref[...]                              # (1, CB) f32
    # one-hot via iota compare; out-of-tile ids match no row
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (TILE_R, CHUNK_B), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (TILE_C, CHUNK_B), 0)
    oh_r = (iota_r == rows - i_r * TILE_R).astype(jnp.float32)     # (TR, CB)
    oh_c = (iota_c == cols - i_c * TILE_C).astype(jnp.float32) * w  # (TC, CB)
    upd = jax.lax.dot_general(
        oh_r,
        oh_c,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (TR, TC)
    out_ref[...] += upd[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ingest_pallas(
    counters, rows, cols, weights, interpret: Optional[bool] = None
):
    """counters (d, wr, wc) f32; rows/cols (d, B) int32; weights (B,) f32.
    Shapes must be pre-padded: wr % TILE_R == wc % TILE_C == B % CHUNK_B == 0
    (ops.py handles padding)."""
    d, wr, wc = counters.shape
    b = rows.shape[1]
    grid = (d, wr // TILE_R, wc // TILE_C, b // CHUNK_B)
    return pl.pallas_call(
        _ingest_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK_B), lambda i, j, k, l: (i, 0, l)),  # rows
            pl.BlockSpec((1, 1, CHUNK_B), lambda i, j, k, l: (i, 0, l)),  # cols
            pl.BlockSpec((1, CHUNK_B), lambda i, j, k, l: (0, l)),        # weights
            pl.BlockSpec((1, TILE_R, TILE_C), lambda i, j, k, l: (i, j, k)),
        ],
        out_specs=pl.BlockSpec((1, TILE_R, TILE_C), lambda i, j, k, l: (i, j, k)),
        out_shape=jax.ShapeDtypeStruct(counters.shape, jnp.float32),
        input_output_aliases={3: 0},
        interpret=resolve_interpret(interpret),
    )(rows[:, None, :], cols[:, None, :], weights[None, :], counters)
