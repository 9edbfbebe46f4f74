"""Pallas TPU kernel: ONE-PASS fused sketch ingest.

Plain ingest makes three separate passes over HBM per batch: the counter
scatter, ``scatter_flows`` for the two flow registers, and (host-side)
``touched_row_keys`` for the incremental-closure plane.  This kernel does
all four updates in a single sweep:

    counters[i, r, c] += w        row_flows[i, r] += w
    col_flows[i, c]   += w        touched[i, r]    = 1

Grid = (d, wr/TILE_R, B/CHUNK_B) with the edge-chunk axis innermost, so the
(TILE_R x wc) counter stripe, its row-flow/touched slices, and the full
col-flow row stay VMEM-resident while every chunk accumulates into them
(input_output_aliasing keeps the updates in place).  Column tiles are the
FULL padded width: col_flows has no row-tile axis, so it accumulates only
on the j == 0 row tile, and splitting columns would either double-count it
or force a second pass — the thing this kernel exists to avoid.

Layout: the one-hots are built transposed, (tile × chunk), so the chunk's
ids stay on the lane axis; indices are (d, 1, B), weights (1, B), and the
registers and bitmap (d, 1, w), whose (1, n) blocks meet the TPU tiling
rule.  The register deltas come out of the MXU too, as (8, CB) @ (n, CB)^T
products whose row 0 (or 1) is the delta — already lane-major like the
registers.  Contractions that carry weights run at ``Precision.HIGHEST``
(weights are not bf16-exact; the counting regime needs exact fp32).

VMEM working set per program (wc = 2048, the widest served):
    4 * TILE_R*wc*4 (counter stripe in and out, double-buffered)
    + 2 * CHUNK_B*wc*4 (weighted one-hot cols and its mask)
    + TILE_R*wc*4 (the update) + CHUNK_B*TILE_R*4 (one-hot rows)
    = 8 MB + 8 MB + 2 MB + 0.5 MB ≈ 19 MB, above the 16 MB default scoped
VMEM limit, so the call raises the limit to VMEM_LIMIT_BYTES.
MXU alignment: TILE_R, CHUNK_B and the padded wc are multiples of 128.

Row ids may be -1 (padding / out-of-shard): the iota compare matches no
row AND the weight is masked to zero, so such slots touch nothing — not
even col_flows.  The ``touched`` output marks every row a VALID slot hashes
to, weight 0 included (ref.py mirrors both rules bit-for-bit).
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
CHUNK_B = 512
LANE = 128  # the padded column width must be a multiple of this
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _nt(a, b):
    """a (m, k) @ b (n, k)^T -> (m, n), exact fp32 products."""
    return jax.lax.dot_general(
        a,
        b,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _fused_kernel(
    rows_ref,
    cols_ref,
    w_ref,
    counters_ref,
    rf_ref,
    cf_ref,
    out_c_ref,
    out_rf_ref,
    out_cf_ref,
    out_t_ref,
    *,
    wc: int,
):
    """One (d, r-tile, b-chunk) program over the full column width."""
    i_j = pl.program_id(1)
    i_b = pl.program_id(2)

    @pl.when(i_b == 0)
    def _init():
        out_c_ref[...] = counters_ref[...]
        out_rf_ref[...] = rf_ref[...]
        out_t_ref[...] = jnp.zeros_like(out_t_ref)

    @pl.when((i_b == 0) & (i_j == 0))
    def _init_cf():
        out_cf_ref[...] = cf_ref[...]

    rows = rows_ref[0]                          # (1, CB) int32, global row ids
    cols = cols_ref[0]
    # -1 rows (padding / out-of-shard) contribute nothing anywhere: the iota
    # compare already misses every row; masking w kills col_flows too.
    valid = (rows >= 0).astype(jnp.float32)
    w = w_ref[...] * valid                      # (1, CB) f32
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (TILE_R, CHUNK_B), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (wc, CHUNK_B), 0)
    oh_r = (iota_r == rows - i_j * TILE_R).astype(jnp.float32)    # (TR, CB)
    oh_cw = (iota_c == cols).astype(jnp.float32) * w              # (wc, CB)
    out_c_ref[...] += _nt(oh_r, oh_cw)[None]                      # (TR, wc)
    # Row 0 of the left operand carries the weights (row-flow delta), row 1
    # the valid-slot indicator: touched = "a valid slot hashed here",
    # weight-independent, so w == 0 edges still mark their row.
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, CHUNK_B), 0)
    lhs = jnp.where(sub == 0, w, jnp.where(sub == 1, valid, 0.0))
    per_row = _nt(lhs, oh_r)                                      # (8, TR)
    out_rf_ref[...] += per_row[0:1][None]
    hit = (per_row[1:2] > 0.0).astype(jnp.float32)
    out_t_ref[...] = jnp.maximum(out_t_ref[...], hit[None])

    @pl.when(i_j == 0)
    def _col_flows():
        ones = (sub == 0).astype(jnp.float32)
        out_cf_ref[...] += _nt(ones, oh_cw)[0:1][None]            # (1, wc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_ingest_pallas(
    counters,
    row_flows,
    col_flows,
    rows,
    cols,
    weights,
    interpret: Optional[bool] = None,
):
    """counters (d, wr, wc) f32; row/col_flows (d, wr)/(d, wc) f32;
    rows/cols (d, B) int32; weights (B,) f32.  Shapes must be pre-padded:
    wr % TILE_R == wc % LANE == B % CHUNK_B == 0 (ops.py handles padding).
    Returns (counters, row_flows, col_flows, touched) with touched (d, wr)
    f32 in {0, 1}."""
    d, wr, wc = counters.shape
    b = rows.shape[1]
    grid = (d, wr // TILE_R, b // CHUNK_B)
    out_c, out_rf, out_cf, out_t = pl.pallas_call(
        functools.partial(_fused_kernel, wc=wc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK_B), lambda i, j, l: (i, 0, l)),  # rows
            pl.BlockSpec((1, 1, CHUNK_B), lambda i, j, l: (i, 0, l)),  # cols
            pl.BlockSpec((1, CHUNK_B), lambda i, j, l: (0, l)),        # weights
            pl.BlockSpec((1, TILE_R, wc), lambda i, j, l: (i, j, 0)),
            pl.BlockSpec((1, 1, TILE_R), lambda i, j, l: (i, 0, j)),   # row_flows
            pl.BlockSpec((1, 1, wc), lambda i, j, l: (i, 0, 0)),       # col_flows
        ],
        out_specs=[
            pl.BlockSpec((1, TILE_R, wc), lambda i, j, l: (i, j, 0)),
            pl.BlockSpec((1, 1, TILE_R), lambda i, j, l: (i, 0, j)),
            pl.BlockSpec((1, 1, wc), lambda i, j, l: (i, 0, 0)),
            pl.BlockSpec((1, 1, TILE_R), lambda i, j, l: (i, 0, j)),   # touched
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, wr, wc), jnp.float32),
            jax.ShapeDtypeStruct((d, 1, wr), jnp.float32),
            jax.ShapeDtypeStruct((d, 1, wc), jnp.float32),
            jax.ShapeDtypeStruct((d, 1, wr), jnp.float32),
        ],
        input_output_aliases={3: 0, 4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
    )(
        rows[:, None, :],
        cols[:, None, :],
        weights[None, :],
        counters,
        row_flows[:, None, :],
        col_flows[:, None, :],
    )
    return out_c, out_rf[:, 0], out_cf[:, 0], out_t[:, 0]
