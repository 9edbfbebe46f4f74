"""Public entry point for the one-pass fused ingest.

Owns padding/unpadding around the Pallas kernel and the platform dispatch:
the compiled kernel on TPU, the bit-identical jnp ref twin elsewhere (the
interpret-mode kernel is a correctness artifact for tests, far too slow to
serve a session from), with ``interpret=True`` forcing the kernel body on
CPU for the bit-equality sweeps.  Where the kernel would run, a padded
column width past ``MAX_FUSED_WC`` is refused rather than served by the
twin, so a session that asks for the fused kernel gets it or an error.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.ingest import pad_to
from repro.kernels import resolve_interpret
from repro.kernels.ingest_fused.kernel import (
    CHUNK_B,
    LANE,
    TILE_R,
    fused_ingest_pallas,
)
from repro.kernels.ingest_fused.ref import fused_ingest_ref

# Past this padded column width the full-width VMEM stripe (counter tile +
# one-hot cols) no longer fits the kernel's VMEM budget.
MAX_FUSED_WC = 2048


def fused_ingest(
    counters,          # (d, wr, wc) f32
    row_flows,         # (d, wr) f32
    col_flows,         # (d, wc) f32
    rows,              # (d, B) int32 — may contain -1 for masked slots
    cols,              # (d, B) int32 — in [0, wc)
    weights,           # (B,) f32
    *,
    interpret: Optional[bool] = None,
):
    """One-pass fused ingest (see kernel.py).  Any shapes up to a padded
    column width of ``MAX_FUSED_WC`` where the kernel runs; returns
    ``(counters, row_flows, col_flows, touched)`` with touched (d, wr)
    bool.  Bit-identical to :func:`fused_ingest_ref` for integer-valued
    weights (property-tested)."""
    d, wr, wc = counters.shape
    weights = weights.astype(jnp.float32)
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    if interpret is None and resolve_interpret():
        return fused_ingest_ref(counters, row_flows, col_flows, rows, cols, weights)
    if wc + (-wc) % LANE > MAX_FUSED_WC:
        raise ValueError(
            f"fused ingest serves column widths up to {MAX_FUSED_WC}, got "
            f"{wc}; open the session with another ingest_backend"
        )
    cp = pad_to(pad_to(counters.astype(jnp.float32), TILE_R, 1), LANE, 2)
    rfp = pad_to(row_flows.astype(jnp.float32), TILE_R, 1)
    cfp = pad_to(col_flows.astype(jnp.float32), LANE, 1)
    rp = pad_to(rows, CHUNK_B, 1, value=-1)
    cl = pad_to(cols, CHUNK_B, 1)
    wp = pad_to(weights, CHUNK_B, 0)  # padded edges carry weight 0
    out_c, out_rf, out_cf, out_t = fused_ingest_pallas(
        cp, rfp, cfp, rp, cl, wp, interpret=interpret
    )
    return (
        out_c[:, :wr, :wc],
        out_rf[:, :wr],
        out_cf[:, :wc],
        out_t[:, :wr] > 0,
    )
