"""jit'd wrapper around the Pallas ingest kernel.

Padding/unpadding and index masking live in ``repro.core.ingest`` (the one
dispatch point for every ingest backend); this module keeps the historical
``sketch_ingest`` entry point for kernel benchmarks and tests.
"""
from __future__ import annotations

from repro.core.ingest import ingest


def sketch_ingest(counters, rows, cols, weights):
    """counters (d, wr, wc) f32 += scatter(rows, cols, weights).  Any shapes;
    equals ref.sketch_ingest_ref exactly for integer-valued weights.
    Interpret-vs-compiled follows the platform (``repro.kernels``)."""
    return ingest(counters, rows, cols, weights, backend="pallas")
