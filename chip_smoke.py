"""Chip smoke test: the gLava serving path, once, on a TPU at the `base` preset.

    python chip_smoke.py                # phases A-C on one chip
    python chip_smoke.py --four-chips   # the mesh session vs one device

Phase A opens ``GraphStream.open("base")`` with default options (so ``auto``
picks the Pallas kernels on a TPU), ingests four batches of 16,384 Zipf edges
with integer weights, and checks a standing subscription (edge, in-flow,
heavy, reach) after every batch and one one-shot ``QueryBatch`` against plain
references: numpy ``np.add.at`` sums over the host copy of the batches for
edge, in-flow and heavy (bit for bit), and the jnp
``reach.transitive_closure`` of the same counters for reach (element for
element).  It then ingests the stream again with ``ingest_backend="scatter"``
and checks that session against the same references.  Phase B runs
``repro.launch.serve`` in-process on an event-time window with a
write-ahead log; phase C runs it in fleet mode.

``--four-chips`` runs only ``GraphStream.open("base", mesh=...)`` on two
``("data", "model")`` meshes of the four chips, each against a
single-device session in the same process: 2 x 2 (two stream shards whose
partial updates merge by ``psum``, two row shards) and 1 x 4 (the layout the
session's placement rule builds for a sketch one chip cannot hold).  On
both, counters, registers and edge, in-flow and heavy answers must be
bit-identical, the counters must span all four devices, and ingest must
resolve to the Pallas kernel per shard.  On 1 x 4 the compiled programs must
also move no counter between chips: the update has no collective, and the
edge query's only one is its ``pmin`` over d x Q values.

Each phase prints one line: resolved backends, compile and wall seconds,
edges ingested, the device's peak bytes in use, and which Pallas kernels
appear as ``tpu_custom_call`` in the programs the phase handed to the
compiler.  The last line is ``{"ok": true, "device": {...}}``.  Without a
TPU, or outside a checkout of this repository, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import re
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / ".smoke_out"

N_BATCHES = 4
BATCH = 16_384
N_NODES = 100_000
# Weights up to 300 are not all bf16-exact (257 is not), so a kernel that
# rounded its matmul operands would fail the bit-exact checks; the stream's
# total weight (~1e7) stays below 2**24, the exact-fp32 counting regime.
MAX_WEIGHT = 300
THETA = 1e-3
# The Pallas kernels main path resolves to on a TPU, by kernel body name.
SESSION_KERNELS = ("_ingest_kernel", "_multi_query_kernel", "_closure_kernel")
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(AssertionError):
    """A phase's output disagrees with its reference."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# plain references
# ---------------------------------------------------------------------------


def make_stream(seed: int, n_edges: int, n_nodes: int = N_NODES):
    from repro.data.graphs import edge_stream

    rng = np.random.default_rng(seed)
    data = edge_stream(n_nodes, n_edges, rng, zipf_a=1.2, max_weight=MAX_WEIGHT)
    return data["src"], data["dst"], data["weight"], rng


def host_hash(family):
    """A hash family's parameters, copied to the host (the session donates
    its device copies with every batch)."""
    return np.asarray(family.a)[:, None], np.asarray(family.b)[:, None], family.w


def buckets(params, keys):
    """(d, n) bucket ids from host hash parameters."""
    from repro.core.hashing import affine_hash_np

    return affine_hash_np(np.asarray(keys, np.uint32)[None, :], *params)


class Reference:
    """Exact sums over the host copy of every batch ingested so far."""

    def __init__(self, sketch):
        self.row_hash = host_hash(sketch.row_hash)
        self.col_hash = host_hash(sketch.col_hash)
        d = sketch.config.depth
        self.wr, self.wc = sketch.config.width_rows, sketch.config.width_cols
        self.d_idx = np.arange(d)[:, None]
        self.rows = np.zeros((d, self.wr))
        self.cols = np.zeros((d, self.wc))
        self.cell_ids = np.zeros(0, np.int64)
        self.cell_w = np.zeros(0)
        self.total = 0.0

    def add(self, src, dst, w):
        w = np.asarray(w, np.float64)
        r, c = buckets(self.row_hash, src), buckets(self.col_hash, dst)
        np.add.at(self.rows, (np.broadcast_to(self.d_idx, r.shape), r), w)
        np.add.at(self.cols, (np.broadcast_to(self.d_idx, c.shape), c), w)
        ids = ((self.d_idx * self.wr + r) * self.wc + c).reshape(-1)
        ids = np.concatenate([self.cell_ids, ids])
        ws = np.concatenate([self.cell_w, np.broadcast_to(w, r.shape).reshape(-1)])
        self.cell_ids, inv = np.unique(ids, return_inverse=True)
        self.cell_w = np.zeros(self.cell_ids.size)
        np.add.at(self.cell_w, inv, ws)
        self.total += float(w.sum())

    def edge(self, src, dst):
        r, c = buckets(self.row_hash, src), buckets(self.col_hash, dst)
        ids = (self.d_idx * self.wr + r) * self.wc + c
        pos = np.clip(np.searchsorted(self.cell_ids, ids), 0, self.cell_ids.size - 1)
        vals = np.where(self.cell_ids[pos] == ids, self.cell_w[pos], 0.0)
        return vals.min(axis=0).astype(np.float32)

    def _flow(self, reg, family, keys):
        h = buckets(family, keys)
        return np.take_along_axis(reg, h, axis=1).min(axis=0).astype(np.float32)

    def in_flow(self, keys):
        return self._flow(self.cols, self.col_hash, keys)

    def out_flow(self, keys):
        return self._flow(self.rows, self.row_hash, keys)

    def heavy(self, keys, theta):
        cut = np.float32(theta) * np.float32(self.total)
        return self.in_flow(keys) > cut, self.out_flow(keys) > cut


def reach_reference(sketch, src, dst):
    """Answers from the jnp closure of the same counters, plus that closure."""
    from repro.core import reach

    closure = reach.transitive_closure(sketch.counters)
    row_hash = host_hash(sketch.row_hash)
    r, c = buckets(row_hash, src), buckets(row_hash, dst)
    d_idx = np.broadcast_to(np.arange(r.shape[0])[:, None], r.shape)
    return np.asarray(closure[d_idx, r, c]).all(axis=0), closure


def workload(src, dst, rng, n_nodes):
    """1,024 edge queries (3/4 of them ingested pairs), 256 in-flow keys, 64
    heavy keys and 64 reach pairs."""
    from repro.api import Query, QueryBatch

    qs = np.concatenate([src[:768], rng.integers(0, n_nodes, 256)]).astype(np.uint32)
    qd = np.concatenate([dst[:768], rng.integers(0, n_nodes, 256)]).astype(np.uint32)
    batch = QueryBatch(
        [
            Query.edge(qs, qd),
            Query.in_flow(qs[:256]),
            Query.heavy(qs[:64], theta=THETA),
            Query.reach(qs[:64], qd[:64]),
        ]
    )
    return batch, qs, qd


def check_answers(tag, results, ref, qs, qd, sketch=None):
    """Edge, in-flow and heavy bit for bit; reach element for element."""
    edge, in_flow, heavy = (r.value for r in results[:3])
    check(
        np.array_equal(np.asarray(edge), ref.edge(qs, qd)),
        f"{tag}: edge answers differ from the np.add.at reference",
    )
    check(
        np.array_equal(np.asarray(in_flow), ref.in_flow(qs[:256])),
        f"{tag}: in-flow answers differ from the np.add.at reference",
    )
    want_in, want_out = ref.heavy(qs[:64], THETA)
    check(
        np.array_equal(np.asarray(heavy[0]), want_in)
        and np.array_equal(np.asarray(heavy[1]), want_out),
        f"{tag}: heavy answers differ from the np.add.at reference",
    )
    if sketch is None:
        return None
    want, closure = reach_reference(sketch, qs[:64], qd[:64])
    check(
        np.array_equal(np.asarray(results[3].value), want),
        f"{tag}: reach answers differ from the jnp closure",
    )
    return closure


# ---------------------------------------------------------------------------
# phases — each returns a report; the sessions it opened die with it
# ---------------------------------------------------------------------------


def phase_session(
    preset="base",
    n_batches=N_BATCHES,
    batch=BATCH,
    n_nodes=N_NODES,
    seed=0,
    **open_kwargs,
):
    """A: the default session (subscription after every batch, then one
    one-shot batch), then the same stream through ``ingest_backend="scatter"``."""
    from repro.api import GraphStream

    src, dst, w, rng = make_stream(seed, n_batches * batch, n_nodes)
    gs = GraphStream.open(preset, **open_kwargs)
    ref = Reference(gs.sketch)
    queries, qs, qd = workload(src, dst, rng, n_nodes)
    sub = gs.subscribe(queries, every=1, name="smoke")
    for i in range(n_batches):
        lo, hi = i * batch, (i + 1) * batch
        gs.ingest(src[lo:hi], dst[lo:hi], w[lo:hi])
        ref.add(src[lo:hi], dst[lo:hi], w[lo:hi])
        events = sub.poll()
        check(len(events) == 1, f"batch {i}: {len(events)} subscription events, want 1")
        check_answers(f"tick {i + 1}", events[0].results, ref, qs, qd, gs.sketch)
    closure = check_answers("one-shot", gs.query(queries), ref, qs, qd, gs.sketch)
    check(
        bool(jnp.array_equal(gs.engine._closure, closure)),
        "the session's cached closure differs from the jnp closure",
    )
    cfg = gs.config
    report = dict(
        sketch=f"{preset}: d={cfg.depth} w={cfg.width_rows}x{cfg.width_cols}",
        ingest_backend=gs.ingest_backend,
        query_backend=gs.engine.backend,
        edges=int(gs.summary()["edges_ingested"]),
    )
    del gs, sub, closure
    gc.collect()

    scatter = GraphStream.open(preset, ingest_backend="scatter")
    for i in range(n_batches):
        lo, hi = i * batch, (i + 1) * batch
        scatter.ingest(src[lo:hi], dst[lo:hi], w[lo:hi])
    check_answers("scatter", scatter.query(queries), ref, qs, qd)
    report["edges"] += int(scatter.summary()["edges_ingested"])
    report["checked"] = f"{n_batches} ticks + one-shot + scatter rerun"
    return report


def phase_serve(wal_dir, width=8192, depth=5, edges=N_BATCHES * BATCH, batch=BATCH):
    """B: ``repro.launch.serve`` on an event-time window with a WAL."""
    from repro.launch import serve

    stats = serve.main(
        [
            "--width", str(width), "--depth", str(depth),
            "--window-slices", "4", "--slice-width", "1.0", "--max-lateness", "0.5",
            "--wal-dir", str(wal_dir),
            "--nodes", str(N_NODES), "--edges", str(edges), "--batch", str(batch),
        ]
    )
    from repro.stream.wal import EdgeMutation, WriteAheadLog

    n_batches = -(-edges // batch)
    check(stats["edges_ingested"] == edges, f"serve ingested {stats['edges_ingested']}")
    # Every acknowledged batch reads back from the log.
    wal = WriteAheadLog(wal_dir)
    logged = [m for m in wal.replay() if isinstance(m, EdgeMutation)]
    wal.close()
    check(len(logged) == n_batches, f"WAL replays {len(logged)} batches of {n_batches}")
    check(
        sum(m.src.size for m in logged) == edges
        and all(np.isfinite(m.timestamps).all() for m in logged),
        "WAL replay lost edges or event times",
    )
    check(stats["subscription_ticks"] >= n_batches, "serve subscription missed ticks")
    check(np.isfinite(stats["watermark"]), "serve watermark is not finite")
    return dict(
        sketch=f"d={depth} w={width}x{width}, window K=4, event time, WAL",
        ingest_backend=stats["ingest_backend"],
        query_backend=stats["query_backend"],
        edges=int(stats["edges_ingested"]),
        checked=f"WAL replays {n_batches} batches, ticks={stats['subscription_ticks']}",
    )


def phase_fleet(tenants=64, width=1024, depth=4, edges=N_BATCHES * BATCH, batch=BATCH):
    """C: ``repro.launch.serve`` in fleet mode."""
    from repro.launch import serve

    stats = serve.main(
        [
            "--tenants", str(tenants), "--width", str(width), "--depth", str(depth),
            "--nodes", str(N_NODES), "--edges", str(edges), "--batch", str(batch),
        ]
    )
    check(stats["edges_ingested"] == edges, f"fleet ingested {stats['edges_ingested']}")
    check(stats["ingest_compiles"] == 1, f"fleet ingest compiled {stats['ingest_compiles']}x")
    check(all(t > 0 for t in stats["sub_ticks"]), "a fleet subscription never ticked")
    return dict(
        sketch=f"fleet T={tenants}: d={depth} w={width}x{width}",
        ingest_backend="fleet-stacked-scatter",
        query_backend="fleet-jnp",
        edges=int(stats["edges_ingested"]),
        checked=f"ingest_compiles=1 sub_ticks={stats['sub_ticks']}",
    )


COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "reduce-scatter", "all-to-all")


def collectives(hlo: str) -> list:
    """The collective instructions of a compiled module's text."""
    return [
        line.strip()
        for line in hlo.splitlines()
        if any(f" {k}(" in line or f" {k}-start(" in line for k in COLLECTIVES)
    ]


def phase_mesh(
    preset="base", shape=(1, 4), n_batches=N_BATCHES, batch=BATCH, n_nodes=N_NODES, seed=0
):
    """A session row-sharded on a ``shape`` ("data", "model") mesh of four
    devices against a single-device session, bit for bit; on a 1 x n mesh
    also the collectives its programs compile to."""
    from repro.api import GraphStream, Query, QueryBatch

    src, dst, w, rng = make_stream(seed, n_batches * batch, n_nodes)
    mesh = jax.make_mesh(shape, ("data", "model"))
    streams, rows = shape
    dist = GraphStream.open(preset, mesh=mesh)
    local = GraphStream.open(preset)
    check(
        dist.ingest_backend == local.ingest_backend,
        f"mesh ingest resolved to {dist.ingest_backend}, one device to {local.ingest_backend}",
    )
    ref = Reference(local.sketch)
    for i in range(n_batches):
        lo, hi = i * batch, (i + 1) * batch
        dist.ingest(src[lo:hi], dst[lo:hi], w[lo:hi])
        local.ingest(src[lo:hi], dst[lo:hi], w[lo:hi])
        ref.add(src[lo:hi], dst[lo:hi], w[lo:hi])
    counters = dist.sketch.counters
    wr = dist.config.width_rows
    shards = counters.addressable_shards
    check(
        len({s.device for s in shards}) == 4,
        f"mesh counters span {len({s.device for s in shards})} devices, want 4",
    )
    check(
        all(s.data.shape[1] == wr // rows for s in shards),
        f"mesh counter shards hold {[s.data.shape for s in shards]}, want w_r/{rows} rows",
    )
    for name in ("counters", "row_flows", "col_flows"):
        check(
            np.array_equal(
                np.asarray(getattr(dist.sketch, name)),
                np.asarray(getattr(local.sketch, name)),
            ),
            f"mesh {name} differ from the single-device session",
        )
    qs = np.concatenate([src[:768], rng.integers(0, n_nodes, 256)]).astype(np.uint32)
    qd = np.concatenate([dst[:768], rng.integers(0, n_nodes, 256)]).astype(np.uint32)
    queries = QueryBatch(
        [Query.edge(qs, qd), Query.in_flow(qs[:256]), Query.heavy(qs[:64], theta=THETA)]
    )
    got, want = dist.query(queries), local.query(queries)
    for g, x in zip(got, want):
        check(
            np.array_equal(np.asarray(g.value), np.asarray(x.value)),
            f"mesh {g.query.family} answers differ from the single-device session",
        )
    check(np.array_equal(np.asarray(got[0].value), ref.edge(qs, qd)), "mesh edge != reference")
    check(
        np.array_equal(np.asarray(got[1].value), ref.in_flow(qs[:256])),
        "mesh in-flow != reference",
    )
    d = dist.config.depth
    layout = f"{streams}x{rows}"
    checked = f"counters on 4 devices, {wr // rows} rows each; bit-identical"
    if streams > 1:
        checked += f"; {streams} stream shards merged by psum"
    else:
        # The programs as compiled for this mesh: the update moves nothing
        # between devices, the edge query one pmin of d x Q values.
        leaves = jax.tree_util.tree_leaves(dist.sketch)
        uniq = tuple(leaves[i] for i in dist._uniq_leaf_idx)
        slots = jax.ShapeDtypeStruct((batch,), jnp.uint32, sharding=dist._replicated)
        vals = jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=dist._replicated)
        update = dist._jit_update_mesh.lower(uniq, slots, slots, vals, slots, vals, slots, vals)
        moved = collectives(update.compile().as_text())
        check(not moved, f"the mesh update compiles to collectives: {moved}")
        keys = jax.ShapeDtypeStruct((qs.size,), jnp.uint32, sharding=dist._replicated)
        edge = dist.engine._fn("edge").lower(dist.sketch, keys, keys).compile().as_text()
        edge = collectives(edge)
        check(
            len(edge) == 1 and " all-reduce(" in edge[0] and f"f32[{d},{qs.size}]" in edge[0],
            f"the edge query compiles to {edge}, want one all-reduce of f32[{d},{qs.size}]",
        )
        checked += (
            f"; update: no collective; edge query: one all-reduce of {d * qs.size * 4} B"
        )
    return dict(
        sketch=f"{preset}: d={d} w={wr}x{dist.config.width_cols}, {layout}",
        ingest_backend=f"mesh:{dist.ingest_backend} vs local:{local.ingest_backend}",
        query_backend=dist.engine.backend,
        edges=int(dist.summary()["edges_ingested"] + local.summary()["edges_ingested"]),
        checked=checked,
    )


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration


def kernels_in(ir_dir: pathlib.Path) -> set:
    """Pallas kernel names that appear as tpu_custom_call in the modules the
    phase handed to the compiler (jax_dump_ir_to writes them before any
    persistent-cache lookup, so a cache hit still shows its program)."""
    found = set()
    for path in ir_dir.glob("*compile.mlir"):
        for line in path.read_text(errors="replace").splitlines():
            if "tpu_custom_call" in line:
                found.update(re.findall(r'kernel_name = "([^"]+)"', line))
    return found


def run_phase(name, fn, clock, expect_kernels=()):
    ir_dir = OUT / "ir" / name
    jax.config.update("jax_dump_ir_to", str(ir_dir))
    c0, t0 = clock.seconds, time.perf_counter()
    report = fn()
    wall = time.perf_counter() - t0
    jax.config.update("jax_dump_ir_to", "")
    gc.collect()
    jax.clear_caches()  # the next phase compiles (and dumps) its own programs
    stats = jax.devices()[0].memory_stats() or {}
    found = kernels_in(ir_dir)
    report = dict(
        phase=name,
        **report,
        compile_s=round(clock.seconds - c0, 3),
        wall_s=round(wall, 3),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_in_use_after=stats.get("bytes_in_use"),
        tpu_custom_call={k: k in found for k in expect_kernels},
    )
    print(f"[smoke] {json.dumps(report)}", flush=True)
    missing = [k for k in expect_kernels if k not in found]
    check(not missing, f"phase {name}: no tpu_custom_call for {missing}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the 2x2 and 1x4 mesh sessions and their single-device comparisons",
    )
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: this is not a checkout of the repository ({e})", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}; "
            "it does not fall back to the CPU",
            file=sys.stderr,
        )
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}", file=sys.stderr)
        return 1
    enable_compile_cache()
    shutil.rmtree(OUT, ignore_errors=True)
    clock = CompileClock()
    if args.four_chips:
        for shape in ((2, 2), (1, 4)):
            run_phase(
                "mesh-{}x{}".format(*shape),
                lambda: phase_mesh(shape=shape),
                clock,
                SESSION_KERNELS[:2],
            )
    else:
        run_phase("A", phase_session, clock, SESSION_KERNELS)
        run_phase("B", lambda: phase_serve(OUT / "wal"), clock, SESSION_KERNELS)
        run_phase("C", phase_fleet, clock)
    device = dict(
        platform=devices[0].platform, kind=devices[0].device_kind, count=len(devices)
    )
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
