"""Ingest work counted from the batches the benchmark sent, independently of
how the program implements ingest.

For a batch of B raw edges with P distinct pairs, U_src distinct sources
and U_dst distinct destinations, any correct implementation reads each raw
edge once (src, dst and weight, 4 bytes each) and reads and writes, in
each of the d sketches, one float32 counter per distinct pair and one
register bucket per distinct endpoint.  That is the least memory traffic
of the batch:

    bytes = 12*B + 8*d*(P + U_src + U_dst)

Its operations are a few adds per byte, so the roofline is the memory bound.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.traffic import Pool


@dataclasses.dataclass(frozen=True)
class BatchWork:
    edges: int
    pairs: int
    sources: int
    destinations: int


def batch_work(pool: Pool, start: int, n: int) -> BatchWork:
    s, d, _ = pool.take(start, n)
    pair = (s.astype(np.uint64) << np.uint64(32)) | d.astype(np.uint64)
    return BatchWork(n, int(np.unique(pair).size), int(np.unique(s).size), int(np.unique(d).size))


def ingest_bytes(work: BatchWork, depth: int) -> int:
    return 12 * work.edges + 8 * depth * (work.pairs + work.sources + work.destinations)
