"""Pallas TPU kernels: batched sketch edge-queries.

Gather ``M[d, r(q), c(q)]`` for a query batch is random access — hostile on
TPU.  Reformulated per (query-chunk × row-tile × col-tile) as masked one-hot
contractions on the MXU:

    vals[q] += Σ_ij OneHot_r[i, q] · M_tile[i, j] · OneHot_c[j, q]
             = colsum( OneHot_r ⊙ (M_tile @ OneHot_c) )

with the one-hots built transposed, (tile × chunk), so the query ids stay on
the lane axis: ids are laid out (d, 1, Q) and answers (·, 1, Q), whose
(1, QB) blocks meet the TPU tiling rule.  The matmul runs at
``Precision.HIGHEST`` because counter values are not bf16-exact.

Two variants share the formulation:

``query_pallas``        grid (d, Q/QB, wr/TR, wc/TC); emits the per-sketch
                        cell values (d, Q) — the Γ merge happens outside.
``multi_query_pallas``  the FUSED multi-query kernel: grid
                        (Q/QB, d, wr/TR, wc/TC) with the d axis *inside* —
                        each query chunk's per-sketch value is accumulated
                        in a VMEM scratch and folded into a running
                        min-reduce as each sketch completes, so the whole
                        f̃_e map/reduce (gather + Γ=min) is one kernel pass
                        and the (d, Q) intermediate never exists in HBM.

VMEM/program: TR*TC*4 + QB*TR*4 + QB*TC*4 ≈ 1.3 MB.
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
TILE_C = 256
CHUNK_Q = 256


def _tile_values(rows_ref, cols_ref, counters_ref, i_r, i_c):
    """(1, QB) partial cell values of one counter tile for a query chunk."""
    rows = rows_ref[0]                         # (1, QB) — this sketch's buckets
    cols = cols_ref[0]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (TILE_R, CHUNK_Q), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (TILE_C, CHUNK_Q), 0)
    oh_r = (iota_r == rows - i_r * TILE_R).astype(jnp.float32)   # (TR, QB)
    oh_c = (iota_c == cols - i_c * TILE_C).astype(jnp.float32)   # (TC, QB)
    mc = jax.lax.dot_general(
        counters_ref[0],
        oh_c,
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                          # (TR, QB)
    return jnp.sum(oh_r * mc, axis=0, keepdims=True)


def _query_kernel(rows_ref, cols_ref, counters_ref, out_ref):
    i_r = pl.program_id(2)
    i_c = pl.program_id(3)

    @pl.when((i_r == 0) & (i_c == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _tile_values(rows_ref, cols_ref, counters_ref, i_r, i_c)[None]


def _multi_query_kernel(rows_ref, cols_ref, counters_ref, out_ref, acc_ref):
    i_d = pl.program_id(1)
    i_r = pl.program_id(2)
    i_c = pl.program_id(3)
    last_r = pl.num_programs(2) - 1
    last_c = pl.num_programs(3) - 1

    @pl.when((i_d == 0) & (i_r == 0) & (i_c == 0))
    def _init_out():
        out_ref[...] = jnp.full_like(out_ref, jnp.inf)

    @pl.when((i_r == 0) & (i_c == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_values(rows_ref, cols_ref, counters_ref, i_r, i_c)

    # Sketch i_d's cell value is complete once its tile sweep finishes —
    # fold it into the running Γ (min over sketches) and move to the next d.
    @pl.when((i_r == last_r) & (i_c == last_c))
    def _gamma_fold():
        out_ref[...] = jnp.minimum(out_ref[...], acc_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def multi_query_pallas(counters, rows, cols, interpret: Optional[bool] = None):
    """Fused f̃_e: (d, wr, wc) counters + (d, Q) buckets -> (Q,) min-merged
    estimates in ONE pass (gather and Γ-min never materialize (d, Q))."""
    d, wr, wc = counters.shape
    q = rows.shape[1]
    grid = (q // CHUNK_Q, d, wr // TILE_R, wc // TILE_C)
    out = pl.pallas_call(
        _multi_query_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK_Q), lambda j, i, k, l: (i, 0, j)),
            pl.BlockSpec((1, 1, CHUNK_Q), lambda j, i, k, l: (i, 0, j)),
            pl.BlockSpec((1, TILE_R, TILE_C), lambda j, i, k, l: (i, k, l)),
        ],
        out_specs=pl.BlockSpec((1, CHUNK_Q), lambda j, i, k, l: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, q), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, CHUNK_Q), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rows[:, None, :], cols[:, None, :], counters)
    return out[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def query_pallas(counters, rows, cols, interpret: Optional[bool] = None):
    """Per-sketch cell values (d, Q) of pre-padded (d, Q) buckets."""
    d, wr, wc = counters.shape
    q = rows.shape[1]
    grid = (d, q // CHUNK_Q, wr // TILE_R, wc // TILE_C)
    return pl.pallas_call(
        _query_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK_Q), lambda i, j, k, l: (i, 0, j)),
            pl.BlockSpec((1, 1, CHUNK_Q), lambda i, j, k, l: (i, 0, j)),
            pl.BlockSpec((1, TILE_R, TILE_C), lambda i, j, k, l: (i, k, l)),
        ],
        out_specs=pl.BlockSpec((1, 1, CHUNK_Q), lambda i, j, k, l: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((d, 1, q), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(rows[:, None, :], cols[:, None, :], counters)[:, 0, :]
