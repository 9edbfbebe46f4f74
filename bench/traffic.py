"""The general traffic generator: one seeded edge pool, one arrival schedule
and one standing-query set per run.

The edges come from the configuration's ``graph`` block: the Graph500
Kronecker generator (``scale``, ``initiator`` A, B, C, D), each tuple one
directed stream edge, vertex labels scrambled by a seeded bijection of
``[0, 2**scale)``, weights uniform in [0, 1).  The mix
(``bench/traffic/<mix>.json``) holds only parameters:

- ``arrivals``: ``saturate`` (a full batch each time the previous call
  returns) or ``poisson`` at ``rate`` edges/s, with the batch ``cap``;
- ``queries``: the standing query set and its cadence.

Edge ``i`` of the stream is ``pool[i mod pool_edges]``, so the pool is
drawn once in set-up and a faster program never outruns the generator.
Everything is drawn from ``--seed`` through one ``SeedSequence``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

POOL_EDGES = 1 << 22
# The fewest due edges a pick-up hands over: the program compiles one
# ingest program per batch length below 1,024 edges, and buckets above.
MIN_BATCH = 1024


def scramble(v: np.ndarray, scale: int, rng: np.random.Generator) -> np.ndarray:
    """A seeded bijection of ``[0, 2**scale)``: two rounds of a multiply by
    an odd number and a right xor-shift, each a bijection mod 2**scale."""
    mask = np.uint64((1 << scale) - 1)
    x = v.astype(np.uint64)
    for _ in range(2):
        m = np.uint64(int(rng.integers(0, 1 << (scale - 1))) * 2 + 1)
        x = (x * m) & mask
        x ^= x >> np.uint64(max(scale // 2, 1))
    return x.astype(np.uint32)


def kronecker(graph: Dict, rng: np.random.Generator, n: int):
    """``n`` Kronecker edges as the Graph500 reference generator draws them:
    at each of ``scale`` levels the source bit is 1 with probability
    C + D, and the destination bit is 1 with probability D / (C + D) or
    B / (A + B) as the source bit is 1 or 0."""
    scale = int(graph["scale"])
    a, b, c, _ = (float(x) for x in graph["initiator"])
    ab = a + b
    c_norm, a_norm = c / (1.0 - ab), a / ab
    src = np.zeros(n, np.uint32)
    dst = np.zeros(n, np.uint32)
    for level in range(scale):
        ii = rng.random(n, dtype=np.float32) > ab
        thr = np.where(ii, np.float32(c_norm), np.float32(a_norm))
        jj = rng.random(n, dtype=np.float32) > thr
        src |= ii.astype(np.uint32) << np.uint32(level)
        dst |= jj.astype(np.uint32) << np.uint32(level)
    return scramble(src, scale, rng), scramble(dst, scale, rng)


@dataclasses.dataclass
class Pool:
    """The run's edge pool."""

    src: np.ndarray
    dst: np.ndarray
    weight: np.ndarray

    @property
    def size(self) -> int:
        return int(self.src.size)

    def index(self, start: int, n: int) -> np.ndarray:
        """Pool rows of stream edges ``start .. start+n-1``."""
        return (np.arange(start, start + n, dtype=np.int64) % self.size).astype(np.int64)

    def take(self, start: int, n: int):
        """(src, dst, weight) of stream edges ``start .. start+n-1``."""
        lo = start % self.size
        if lo + n <= self.size:
            sl = slice(lo, lo + n)
            return self.src[sl], self.dst[sl], self.weight[sl]
        idx = self.index(start, n)
        return self.src[idx], self.dst[idx], self.weight[idx]


class Seeds:
    """Independent generators for each part of a run, from one ``--seed``."""

    PARTS = ("pool", "queries", "arrivals", "session", "sample")

    def __init__(self, seed: int):
        children = np.random.SeedSequence(int(seed) % (1 << 64)).spawn(len(self.PARTS))
        self._seqs = dict(zip(self.PARTS, children))

    def rng(self, part: str) -> np.random.Generator:
        return np.random.default_rng(self._seqs[part])

    def session_seed(self) -> int:
        """A 31-bit seed for the program's own hash family."""
        return int(self.rng("session").integers(0, (1 << 31) - 1))


def make_pool(graph: Dict, seeds: Seeds, pool_edges: int = POOL_EDGES) -> Pool:
    rng = seeds.rng("pool")
    src, dst = kronecker(graph, rng, pool_edges)
    weight = rng.random(pool_edges, dtype=np.float32)
    return Pool(src, dst, weight)


@dataclasses.dataclass
class QuerySet:
    """The standing subscription's queries."""

    edge_src: np.ndarray
    edge_dst: np.ndarray
    in_flow: np.ndarray
    heavy: np.ndarray
    theta: float
    reach_src: np.ndarray
    reach_dst: np.ndarray


def make_queries(mix: Dict, graph: Dict, pool: Pool, seeds: Seeds) -> QuerySet:
    """The mix's standing query set: edge pairs drawn partly from the
    stream's own pairs and partly at random, in-flow and heavy keys taken
    from those sources, and reach pairs that join the source of one stream
    edge to the destination of another (multi-hop paths)."""
    q = mix["queries"]
    rng = seeds.rng("queries")
    vertices = 1 << int(graph["scale"])
    n_edge, n_stream = int(q["edge_pairs"]), int(q["edge_from_stream"])
    pick = rng.choice(pool.size, size=n_stream, replace=False)
    es = np.concatenate([pool.src[pick], rng.integers(0, vertices, n_edge - n_stream)])
    ed = np.concatenate([pool.dst[pick], rng.integers(0, vertices, n_edge - n_stream)])
    n_reach = int(q.get("reach_pairs", 0))
    ra = rng.choice(pool.size, size=n_reach, replace=False)
    rb = rng.choice(pool.size, size=n_reach, replace=False)
    return QuerySet(
        edge_src=es.astype(np.uint32),
        edge_dst=ed.astype(np.uint32),
        in_flow=es[: int(q["in_flow_keys"])].astype(np.uint32),
        heavy=es[: int(q["heavy_keys"])].astype(np.uint32),
        theta=float(q["heavy_theta"]),
        reach_src=pool.src[ra].astype(np.uint32),
        reach_dst=pool.dst[rb].astype(np.uint32),
    )


class Arrivals:
    """When each edge of a schedule is due, and how many a pick-up hands over.

    ``saturate``: every pick-up hands over ``cap`` edges, due at once.
    ``poisson``: edge ``i`` is due at a precomputed time (exponential gaps
    at ``rate`` edges/s from the schedule's start); a pick-up hands over
    every due edge up to ``cap``, first waiting, if fewer than MIN_BATCH
    are due, until that many are.  The schedule never waits for the system.
    """

    def __init__(self, mix: Dict, seeds: Seeds, seconds: float):
        a = mix["arrivals"]
        self.mode = a["mode"]
        self.cap = int(a["cap"])
        self.rate = float(a.get("rate", 0.0))
        self.due: Optional[np.ndarray] = None
        if self.mode == "poisson":
            n = int(self.rate * (seconds + 10.0)) + 2 * self.cap
            gaps = seeds.rng("arrivals").exponential(1.0 / self.rate, n)
            self.due = np.cumsum(gaps)
        elif self.mode != "saturate":
            raise ValueError(f"unknown arrival mode {self.mode!r}")

    def pickup(self, handed: int, now: float) -> tuple:
        """(edges to hand over, seconds to wait first) at schedule time
        ``now``, with ``handed`` edges of the schedule handed over so far."""
        if self.mode == "saturate":
            return self.cap, 0.0
        due = self.due
        if handed + MIN_BATCH > due.size:
            raise RuntimeError("the arrival schedule ran out; lengthen it")
        ready = int(np.searchsorted(due, now, side="right")) - handed
        if ready >= MIN_BATCH:
            return min(ready, self.cap), 0.0
        return 0, float(due[handed + MIN_BATCH - 1]) - now

    def due_before(self, t: float) -> int:
        """Edges due at or before schedule time ``t`` (poisson only)."""
        return int(np.searchsorted(self.due, t, side="right"))
