"""jit'd wrappers for the edge-query kernels: pad, run, unpad, Γ-merge (min).

Two entry points mirror the two kernels: :func:`edge_query_cells` (per-sketch
values, min applied here in jnp) and :func:`edge_query_min` (the FUSED
multi-query kernel — the min-reduce happens inside the kernel pass, used by
``repro.core.query_engine.QueryEngine`` on its ``pallas`` backend).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.ingest import pad_to
from repro.kernels.query.kernel import (
    CHUNK_Q,
    TILE_C,
    TILE_R,
    multi_query_pallas,
    query_pallas,
)


def _pad_all(counters, rows, cols):
    cp = pad_to(pad_to(counters.astype(jnp.float32), TILE_R, 1), TILE_C, 2)
    rp = pad_to(rows.astype(jnp.int32), CHUNK_Q, 1)
    cl = pad_to(cols.astype(jnp.int32), CHUNK_Q, 1)
    return cp, rp, cl


def edge_query_cells(counters, rows, cols, interpret: Optional[bool] = None):
    """Per-sketch cell values (d, Q) — matches ref.edge_query_ref exactly."""
    q = rows.shape[1]
    cp, rp, cl = _pad_all(counters, rows, cols)
    out = query_pallas(cp, rp, cl, interpret=interpret)
    return out[:, :q]


def edge_query_min(counters, rows, cols, interpret: Optional[bool] = None):
    """Fused min-merged estimates (Q,) — matches ref.edge_query_min_ref.
    Padded queries hit bucket (0, 0) and are sliced away."""
    q = rows.shape[1]
    cp, rp, cl = _pad_all(counters, rows, cols)
    return multi_query_pallas(cp, rp, cl, interpret=interpret)[:q]


def edge_query(sketch, src_keys, dst_keys, interpret: Optional[bool] = None):
    """Full f̃_e path on the fused kernel: hash → gather+min in one pass."""
    r, c = sketch.hash_edges(src_keys, dst_keys)
    return edge_query_min(sketch.counters, r, c, interpret=interpret)
