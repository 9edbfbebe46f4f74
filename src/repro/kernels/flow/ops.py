"""jit'd wrapper for the flow kernel + the full point-query path."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ingest import pad_to
from repro.kernels.flow.kernel import TILE_C, TILE_R, flows_pallas


def flows(counters, interpret: Optional[bool] = None):
    """(d, wr, wc) -> (row_sums (d, wr), col_sums (d, wc))."""
    d, wr, wc = counters.shape
    cp = pad_to(pad_to(counters.astype(jnp.float32), TILE_R, 1), TILE_C, 2)
    rs, cs = flows_pallas(cp, interpret=interpret)
    return rs[:, :wr], cs[:, :wc]


def node_in_flow(sketch, keys, interpret: Optional[bool] = None):
    _, col_sums = flows(sketch.counters, interpret=interpret)
    h = sketch.col_hash(keys)
    return jnp.min(jnp.take_along_axis(col_sums, h, axis=1), axis=0)


def node_out_flow(sketch, keys, interpret: Optional[bool] = None):
    row_sums, _ = flows(sketch.counters, interpret=interpret)
    h = sketch.row_hash(keys)
    return jnp.min(jnp.take_along_axis(row_sums, h, axis=1), axis=0)
