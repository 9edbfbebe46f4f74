"""The system under test, driven through its public entry point.

A configuration's ``kind`` picks the entry: ``graphstream`` drives one
``GraphStream`` (``ingest``, then ``Subscription.poll``), opened with the
program's defaults and handed nothing but the generated edges and queries.
"""
from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from bench.traffic import Pool, QuerySet


def materialise(results) -> Dict[str, np.ndarray]:
    """An event's request-ordered results as host arrays, by family."""
    out: Dict[str, np.ndarray] = {}
    for res in results:
        fam, v = res.family, res.value
        if fam == "heavy":
            out["heavy_in"], out["heavy_out"] = np.asarray(v[0]), np.asarray(v[1])
        else:
            out[fam] = np.asarray(v)
    return out


def _query_batch(qs: QuerySet):
    from repro.api import Query, QueryBatch

    queries = [
        Query.edge(qs.edge_src, qs.edge_dst),
        Query.in_flow(qs.in_flow),
        Query.heavy(qs.heavy, theta=qs.theta),
    ]
    if qs.reach_src.size:
        queries.append(Query.reach(qs.reach_src, qs.reach_dst))
    return QueryBatch(queries)


def sketch_config(config: Dict):
    from repro.core.sketch import SketchConfig

    sk = config["sketch"]
    return SketchConfig(
        depth=int(sk["depth"]),
        width_rows=int(sk["width_rows"]),
        width_cols=int(sk["width_cols"]),
        directed=bool(sk.get("directed", True)),
    )


class StreamCell:
    """One ``GraphStream`` with one standing subscription."""

    def __init__(self, config: Dict, pool: Pool, queries: QuerySet, seed: int, every: int):
        from repro.api import GraphStream

        self.pool = pool
        self.gs = GraphStream(sketch_config(config), seed=seed)
        self.sub = self.gs.subscribe(_query_batch(queries), every=every, name="bench")

    def ingest(self, start: int, n: int) -> None:
        s, d, w = self.pool.take(start, n)
        self.gs.ingest(s, d, w)

    def poll(self) -> List[tuple]:
        """[(epoch, answers)] of every event delivered since the last poll."""
        events = self.sub.poll()
        for _ in self.gs.events():  # keep the session-wide feed from overflowing
            pass
        return [(ev.epoch, materialise(ev.results)) for ev in events]

    def counters(self) -> Dict[str, int]:
        eng = self.gs.engine
        return dict(
            closure_full=int(eng.closure_refreshes),
            closure_incremental=int(eng.closure_incremental_refreshes),
            events_dropped=int(self.sub.events_dropped + self.gs.events_dropped),
            edges_ingested=int(self.gs.stats.edges_ingested),
        )

    def close(self) -> None:
        self.sub.cancel()
        del self.gs, self.sub
        gc.collect()


KINDS = {"graphstream": StreamCell}


def open_cell(config: Dict, pool: Pool, queries: QuerySet, seed: int, every: int):
    kind = config["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown session kind {kind!r} (want {sorted(KINDS)})")
    return KINDS[kind](config, pool, queries, seed, every)
