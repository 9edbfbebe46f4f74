"""Pallas TPU kernel: one boolean-matrix-squaring step of transitive closure
(paper Section 4.3's reach(), TPU-native — DESIGN.md Section 2).

out = A OR (A @ A > 0), blocked matmul with OR-semantics accumulation:
grid (w/TI, w/TJ, w/TK) with the contraction axis innermost; the saturate
(>0 → 1) happens on the last k-step so intermediate sums can use plain fp32
adds on the MXU.  ops.py iterates ceil(log2(w)) squarings.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE = 256


def _closure_kernel(a_row_ref, a_col_ref, a_diag_ref, out_ref, *, n_k):
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jax.lax.dot_general(
        a_row_ref[...],
        a_col_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out_ref[...] += acc

    @pl.when(i_k == n_k - 1)
    def _saturate():
        got = (out_ref[...] > 0.0) | (a_diag_ref[...] > 0.0)
        out_ref[...] = got.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def closure_step_pallas(a, interpret: Optional[bool] = None):
    """One squaring step for (w, w) f32 0/1 adjacency; w % TILE == 0."""
    w = a.shape[0]
    n_k = w // TILE
    grid = (w // TILE, w // TILE, n_k)
    return pl.pallas_call(
        functools.partial(_closure_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, k)),  # A row-block
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (k, j)),  # A col-block
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, j)),  # A (for OR)
        ],
        out_specs=pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((w, w), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a, a, a)
