"""END-TO-END DRIVER (the paper's kind is a streaming data structure, so the
e2e deliverable is a summarization service, not a training run): a
network-monitoring service summarizing a high-rate Zipf edge stream through
one :class:`repro.api.GraphStream` session — a live mixed query workload
issued as heterogeneous `QueryBatch`es (planned into one engine dispatch
per family), sliding time windows, and accuracy accounting against exact
ground truth.

Run: PYTHONPATH=src python examples/stream_summarize.py [--edges 400000]
"""
import argparse
import collections
import time

import numpy as np

from repro.api import GraphStream, Query, QueryBatch, SketchConfig
from repro.data.graphs import edge_stream


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=50_000)
    ap.add_argument("--edges", type=int, default=400_000)
    ap.add_argument("--batch", type=int, default=40_000)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--depth", type=int, default=4)
    args = ap.parse_args()

    cfg = SketchConfig(depth=args.depth, width_rows=args.width, width_cols=args.width)
    gs = GraphStream.open(cfg, ingest_backend="scatter")
    rng = np.random.default_rng(0)
    stream = edge_stream(args.nodes, args.edges, rng, zipf_a=1.3)

    exact_edges = collections.Counter()
    t_start = time.time()
    abs_err, rel_err = [], []

    for lo in range(0, args.edges, args.batch):
        hi = min(args.edges, lo + args.batch)
        s, d, w = stream["src"][lo:hi], stream["dst"][lo:hi], stream["weight"][lo:hi]
        gs.ingest(s, d, w)
        for si, di, wi in zip(s, d, w):
            exact_edges[(int(si), int(di))] += float(wi)

        # live workload: hottest-pair edge frequencies + heavy-hitter watch +
        # reachability, as ONE planned mixed batch
        hot = [p for p, _ in exact_edges.most_common(64)]
        qs = np.asarray([p[0] for p in hot], np.uint32)
        qd = np.asarray([p[1] for p in hot], np.uint32)
        est_r, _, _ = gs.query(QueryBatch([
            Query.edge(qs, qd),
            Query.heavy(np.arange(0, 128, dtype=np.uint32),
                        theta=0.02),  # heavy = > 2% of total stream weight
            Query.reach(qs[:32], qd[:32]),
        ]))
        est = np.asarray(est_r.value)
        exact = np.asarray([exact_edges[p] for p in hot])
        abs_err.extend(np.abs(est - exact).tolist())
        rel_err.extend((np.abs(est - exact) / exact).tolist())
        assert np.all(est >= exact - 1e-4), "over-estimate invariant violated"

    gs.flush()
    wall = time.time() - t_start
    st = gs.summary()
    # exact per-edge counters for this stream would need one counter per
    # DISTINCT edge and keep GROWING with the stream; the sketch is constant.
    n_distinct = len(exact_edges)
    eps, delta = cfg.error_bound()
    print(
        f"[stream_summarize] {args.edges:,} edges in {wall:.1f}s wall | "
        f"{args.edges / wall:,.0f} edges/s | "
        f"{st['queries_served']:,} queries, {st['queries_served'] / wall:,.0f}/s | "
        f"{st['closure_refreshes']:.0f} closure refreshes"
    )
    print(
        f"[stream_summarize] sketch space {cfg.space_bytes()/1e6:.1f} MB "
        f"(CONSTANT) vs exact hash-map ≥{n_distinct*24/1e6:.1f} MB and growing "
        f"({n_distinct:,} distinct edges so far) | hot-edge mean-rel-err "
        f"{np.mean(rel_err)*100:.2f}% | over-estimate invariant held "
        f"(paper bound: ε={eps:.1e}, δ={delta:.1e})"
    )


if __name__ == "__main__":
    main()
