"""Pallas TPU kernel: CountSketch compression of a gradient vector.

The sketched-all-reduce path (repro.train.compression) compresses a flat
gradient into a (d, w) signed table.  Scatter → one-hot MXU matmul, same
adaptation as the ingest kernel:

    table[d] += (OneHot_buckets ⊙ sign)^T @ grad_chunk

Grid (d, w/TW, n/CN) with the chunk axis innermost (accumulation).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE_W = 256
CHUNK_N = 1024


def _cs_kernel(h_ref, s_ref, v_ref, out_ref):
    i_w = pl.program_id(1)
    i_n = pl.program_id(2)

    @pl.when(i_n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    h = h_ref[0, :]                       # (CN,)
    s = s_ref[0, :].astype(jnp.float32)
    v = v_ref[...]                        # (CN,)
    local = h - i_w * TILE_W
    iota = jax.lax.broadcasted_iota(jnp.int32, (CHUNK_N, TILE_W), 1)
    oh = (iota == local[:, None]).astype(jnp.float32)  # (CN, TW)
    contrib = jax.lax.dot_general(
        oh * (s * v)[:, None],
        jnp.ones((CHUNK_N, 1), jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0]                                # (TW,) column sums
    out_ref[...] += contrib[None]


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def countsketch_pallas(vec, h, s, width: int, interpret: Optional[bool] = None):
    """vec (n,) f32; h (d, n) int32; s (d, n) int32 ±1 -> (d, width) f32.
    width % TILE_W == 0 and n % CHUNK_N == 0 (ops.py pads)."""
    d, n = h.shape
    grid = (d, width // TILE_W, n // CHUNK_N)
    return pl.pallas_call(
        _cs_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, CHUNK_N), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, CHUNK_N), lambda i, j, k: (i, k)),
            pl.BlockSpec((CHUNK_N,), lambda i, j, k: (k,)),
        ],
        out_specs=pl.BlockSpec((1, TILE_W), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d, width), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(h, s, vec)
