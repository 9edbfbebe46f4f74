"""Pallas TPU kernel: fused row+column flow reductions over all d sketches.

One pass over the (d, wr, wc) counters produces BOTH the out-flow (row sums)
and in-flow (column sums) tables — the heavy-hitter monitor (paper
Section 4.2) reads these once per refresh instead of reducing per query.
Grid (d, wr/TR, wc/TC); each program reduces its tile along both axes and
accumulates into the two outputs.
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


def _flow_kernel(counters_ref, out_row_ref, out_col_ref):
    i_r = pl.program_id(1)
    i_c = pl.program_id(2)

    @pl.when(i_c == 0)
    def _init_row():
        out_row_ref[...] = jnp.zeros_like(out_row_ref)

    @pl.when(i_r == 0)
    def _init_col():
        out_col_ref[...] = jnp.zeros_like(out_col_ref)

    tile = counters_ref[0]  # (TR, TC)
    out_row_ref[...] += jnp.sum(tile, axis=1)[None]
    out_col_ref[...] += jnp.sum(tile, axis=0)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def flows_pallas(counters, interpret: Optional[bool] = None):
    d, wr, wc = counters.shape
    grid = (d, wr // TILE_R, wc // TILE_C)
    return pl.pallas_call(
        _flow_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, TILE_R, TILE_C), lambda i, j, k: (i, j, k))],
        out_specs=[
            pl.BlockSpec((1, TILE_R), lambda i, j, k: (i, j)),
            pl.BlockSpec((1, TILE_C), lambda i, j, k: (i, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, wr), jnp.float32),
            jax.ShapeDtypeStruct((d, wc), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(counters)
