"""Serving driver: ``python -m repro.launch.serve`` runs a gLava
:class:`repro.api.GraphStream` session against a synthetic network-traffic
stream with a mixed query workload served as ONE standing subscription —
registered (and planner-compiled) once before the stream starts, then
re-evaluated automatically every ``--every`` ingest batches, with
reachability refreshed incrementally from each batch's touched rows —
and prints throughput/accuracy stats.

``--tenants T`` switches to FLEET mode: the same synthetic stream is
tagged with zipf-distributed tenant ids and served by one
:class:`repro.fleet.SketchFleet` — every mixed batch is a single stacked
device dispatch, a few hot tenants carry standing subscriptions, and the
driver prints fleet-wide throughput plus the one-compile ingest cache
stat (DESIGN.md Section 11).

``main(argv)`` also returns the stats it prints, so an in-process caller
(``chip_smoke.py``) can check them."""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import GraphStream, Query, QueryBatch, SketchConfig
from repro.core.ingest import BACKENDS
from repro.core.query_engine import QUERY_BACKENDS
from repro.data.graphs import edge_stream
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--edges", type=int, default=500_000)
    ap.add_argument("--batch", type=int, default=50_000)
    ap.add_argument("--window-slices", type=int, default=0)
    ap.add_argument(
        "--every",
        type=int,
        default=1,
        help="re-evaluate the standing workload every k ingest batches",
    )
    ap.add_argument(
        "--ingest-backend",
        default="auto",
        choices=["auto", *BACKENDS],
        help="auto = pallas on TPU, scatter elsewhere (REPRO_INGEST_BACKEND overrides)",
    )
    ap.add_argument(
        "--query-backend",
        default="auto",
        choices=["auto", *QUERY_BACKENDS],
        help="auto = fused pallas multi-query kernel on TPU, jnp elsewhere "
        "(REPRO_QUERY_BACKEND overrides)",
    )
    ap.add_argument(
        "--tenants",
        type=int,
        default=0,
        help="serve T tenants as one SketchFleet (0 = single session)",
    )
    ap.add_argument(
        "--wal-dir",
        default=None,
        help="write-ahead-log directory: every batch is durably logged "
        "before its device dispatch (per-tenant lanes in fleet mode)",
    )
    ap.add_argument(
        "--slice-width",
        type=float,
        default=0.0,
        help="event-time slice width: with --window-slices, the stream "
        "carries per-edge timestamps and the watermark drives advances",
    )
    ap.add_argument(
        "--max-lateness",
        type=float,
        default=0.0,
        help="bounded out-of-orderness: edges older than the watermark "
        "minus this are late (retracted via the turnstile-delete path)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = SketchConfig(depth=args.depth, width_rows=args.width, width_cols=args.width)
    if args.tenants:
        return _serve_fleet(cfg, args)
    stream = GraphStream.open(
        cfg,
        window_slices=args.window_slices or None,
        ingest_backend=args.ingest_backend,
        query_backend=args.query_backend,
        wal_dir=args.wal_dir,
        slice_width=args.slice_width or None,
        max_lateness=args.max_lateness if args.slice_width else None,
    )
    rng = np.random.default_rng(0)
    data = edge_stream(args.nodes, args.edges, rng, zipf_a=1.2)
    ts_all = None
    if args.slice_width:
        # Synthetic event time: one slice per ingest batch, with bounded
        # out-of-orderness (uniform lag within --max-lateness) so the
        # watermark path and late routing are actually exercised.
        base = np.arange(args.edges, dtype=np.float64) * (
            args.slice_width / args.batch
        )
        ts_all = base - rng.uniform(0.0, max(args.max_lateness, 0.0), args.edges)
        ts_all = np.maximum(ts_all, 0.0)

    # The monitoring workload is STANDING: the same mixed batch re-asked
    # after every ingest batch.  Register it once — the planner compiles it
    # to one fused dispatch per family — and let the session re-evaluate it
    # on mutation, emitting timestamped events.
    qs = rng.integers(0, args.nodes, 1024).astype(np.uint32)
    qd = rng.integers(0, args.nodes, 1024).astype(np.uint32)
    workload = QueryBatch(
        [
            Query.edge(qs, qd),
            Query.in_flow(qs[:256]),
            Query.heavy(qs[:64], theta=0.01),
            Query.reach(qs[:64], qd[:64]),
        ]
    )
    sub = stream.subscribe(workload, every=args.every, name="mixed-workload")

    t0 = time.perf_counter()
    for lo in range(0, args.edges, args.batch):
        hi = min(args.edges, lo + args.batch)
        stream.ingest(
            data["src"][lo:hi],
            data["dst"][lo:hi],
            data["weight"][lo:hi],
            timestamps=None if ts_all is None else ts_all[lo:hi],
        )
    stream.flush()
    wall_s = time.perf_counter() - t0

    ticks = sub.poll()
    stats = stream.summary()
    stats.update(wall_s=wall_s, edges_per_s=args.edges / wall_s)
    print("[serve] " + " ".join(f"{k}={v:,.1f}" for k, v in stats.items()))
    print(
        f"[serve] subscription {sub.name!r}: {sub.ticks} ticks "
        f"({len(ticks)} events pending), last epoch {ticks[-1].epoch if ticks else '-'}, "
        f"closure full={stream.engine.closure_refreshes} "
        f"incremental={stream.engine.closure_incremental_refreshes}"
    )
    stats.update(
        ingest_backend=stream.ingest_backend, query_backend=stream.engine.backend
    )
    return stats


def _serve_fleet(cfg: SketchConfig, args) -> dict:
    from repro.fleet import SketchFleet

    fleet = SketchFleet.open(
        cfg,
        capacity=args.tenants,
        window_slices=args.window_slices or None,
        wal_dir=args.wal_dir,
    )
    rng = np.random.default_rng(0)
    data = edge_stream(args.nodes, args.edges, rng, zipf_a=1.2)
    # Skewed tenant load — a few hot tenants dominate, like real fleets.
    ids = (rng.zipf(1.3, args.edges) - 1) % args.tenants

    # Standing workloads on the three hottest tenants.
    qs = rng.integers(0, args.nodes, 256).astype(np.uint32)
    qd = rng.integers(0, args.nodes, 256).astype(np.uint32)
    workload = QueryBatch(
        [
            Query.edge(qs[:64], qd[:64]),
            Query.in_flow(qs[:64]),
            Query.reach(qs[:16], qd[:16]),
        ]
    )
    subs = [
        fleet.tenant(t).subscribe(workload, every=args.every, name=f"tenant-{t}")
        for t in range(min(3, args.tenants))
    ]

    t0 = time.perf_counter()
    for lo in range(0, args.edges, args.batch):
        hi = min(args.edges, lo + args.batch)
        fleet.ingest_mixed(
            ids[lo:hi],
            data["src"][lo:hi],
            data["dst"][lo:hi],
            data["weight"][lo:hi],
        )
    fleet.flush()
    wall_s = time.perf_counter() - t0

    stats = fleet.summary()
    stats.update(wall_s=wall_s, edges_per_s=args.edges / wall_s)
    print("[serve-fleet] " + " ".join(f"{k}={v:,.1f}" for k, v in stats.items()))
    stats.update(
        ingest_compiles=fleet._ingest._cache_size(),
        sub_ticks=[s.ticks for s in subs],
    )
    print(
        f"[serve-fleet] ingest compiles={stats['ingest_compiles']} "
        f"dispatches={fleet._ingest.dispatches} "
        f"subs={stats['sub_ticks']} ticks"
    )
    return stats


if __name__ == "__main__":
    main()
