"""The session's own profiler spans (``glava.*``) and the names of its
device programs, as a profile shows them: the span tree of ingest and the
standing-query tick with the stats each span carries, every program the
tick runs named ``glava_query_*``, and the ingest programs named
``_update*``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import GraphStream, Query, QueryBatch

BATCHES, EDGES, NODES = 3, 2048, 48


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A ``smoke`` session with an every-batch subscription, three batches
    ingested under the profiler; returns the session and the host events of
    the main thread as (name, start, end, stats), sorted."""
    rng = np.random.default_rng(0)
    gs = GraphStream.open("smoke")
    keys = np.arange(300, dtype=np.uint32)
    gs.subscribe(
        QueryBatch([
            Query.edge(keys, keys[::-1]),
            Query.in_flow(keys[:100]),
            Query.heavy(keys[:64], theta=0.01),
            Query.reach(keys[:5], keys[5:10]),
        ]),
        every=1,
    )
    batches = [rng.integers(0, NODES, (2, EDGES)).astype(np.uint32) for _ in range(BATCHES)]
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        for s, d in batches:
            gs.ingest(s, d)
    (path,) = out.rglob("*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            names = [e.name for e in line.events]
            if "glava.ingest" in names:
                events = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats) if e.name.startswith("glava.") else {})
                    for e in line.events
                ]
    return gs, sorted(events, key=lambda e: (e[1], -e[2]))


def _inside(events, outer, prefix):
    return [e for e in events if e[0].startswith(prefix) and outer[1] <= e[1] and e[2] <= outer[2] and e is not outer]


def _children(events, outer):
    """The ``glava.*`` spans directly inside ``outer``."""
    inner = _inside(events, outer, "glava.")
    return [e for e in inner if not any(o is not e and o[1] <= e[1] and e[2] <= o[2] for o in inner)]


def test_each_batch_is_one_ingest_span_holding_one_tick(traced):
    _, events = traced
    ingests = [e for e in events if e[0] == "glava.ingest"]
    assert [e[3] for e in ingests] == [{"epoch": k, "edges": EDGES} for k in range(1, BATCHES + 1)]
    for ingest in ingests:
        children = _children(events, ingest)
        names = [e[0] for e in children]
        assert names == [
            "glava.ingest.encode", "glava.ingest.preagg", "glava.ingest.touched",
            "glava.ingest.transfer", "glava.ingest.dispatch", "glava.tick",
        ]
        by = dict(zip(names, children))
        pre = by["glava.ingest.preagg"][3]
        assert set(pre) == {"pairs", "sources", "destinations"}
        assert pre["pairs"] <= EDGES and pre["sources"] <= NODES and pre["destinations"] <= NODES
        assert by["glava.ingest.transfer"][3]["slots"] >= pre["pairs"]
        assert by["glava.tick"][3] == {"epoch": ingest[3]["epoch"], "subscriptions": 1}


def test_the_tick_spans_its_flush_closure_plan_and_emit(traced):
    _, events = traced
    ticks = [e for e in events if e[0] == "glava.tick"]
    assert len(ticks) == BATCHES
    kinds = []
    for tick in ticks:
        children = _children(events, tick)
        assert [e[0] for e in children] == [
            "glava.tick.flush", "glava.tick.closure", "glava.tick.plan", "glava.tick.emit",
        ]
        kinds.append(children[1][3]["kind"])
        plan = children[2]
        assert plan[3] == {"subscription": 0}
        families = {e[0]: e[3] for e in _children(events, plan)}
        assert families == {
            "glava.query.edge": {"queries": 300, "padded": 512},
            "glava.query.in_flow": {"queries": 100, "padded": 256},
            "glava.query.heavy": {"queries": 64, "padded": 256},
            "glava.query.reach": {"queries": 5, "padded": 256},
        }
    assert kinds == ["full"] + ["incremental"] * (BATCHES - 1)


def test_no_span_without_its_plane(traced):
    _, events = traced
    names = {e[0] for e in events}
    assert "glava.ingest.wal" not in names  # the session has no WAL


def _programs(events, outer):
    """The jitted programs called inside ``outer``, leaving out the calls a
    program's first trace makes inside it."""
    calls = _inside(events, outer, "PjitFunction(")
    top = [e for e in calls if not any(o is not e and o[1] <= e[1] and e[2] <= o[2] for o in calls)]
    return {e[0][len("PjitFunction("):-1] for e in top}


def test_the_tick_runs_only_query_programs_and_ingest_only_update_programs(traced):
    _, events = traced
    for tick in (e for e in events if e[0] == "glava.tick"):
        programs = _programs(events, tick)
        assert {"glava_query_edge", "glava_query_in_flow", "glava_query_heavy_rel_vec",
                "glava_query_reach_pre"} <= programs
        assert all(p.startswith("glava_query_") for p in programs), programs
    for dispatch in (e for e in events if e[0] == "glava.ingest.dispatch"):
        assert _programs(events, dispatch) == {"_update_pre"}


def test_lowered_module_names(traced):
    gs, _ = traced
    sketch = gs.sketch
    leaves = jax.tree_util.tree_leaves(sketch)
    uniq = tuple(leaves[i] for i in gs._uniq_leaf_idx)
    keys = jnp.arange(256, dtype=jnp.uint32)
    weights = jnp.ones(256, jnp.float32)
    for fn, args in (
        (gs._jit_update, (keys, keys, weights)),
        (gs._jit_update_pre, (keys, keys, weights, keys, weights, keys, weights)),
    ):
        text = fn.lower(uniq, *args).as_text()
        assert "_update" in text.split("\n", 1)[0]
    engine = gs.engine
    for family, args in (
        ("edge", (keys, keys)),
        ("in_flow", (keys,)),
        ("heavy_rel_vec", (keys, weights)),
    ):
        head = engine._fn(family).lower(sketch, *args).as_text().split("\n", 1)[0]
        assert f"@jit_glava_query_{family} " in head, head


def test_dispatch_carries_the_ingest_kernels_grid_steps(traced, tmp_path):
    """A session on the Pallas ingest kernel puts the kernel's static grid
    length on ``glava.ingest.dispatch``; a scatter session puts nothing."""
    from repro.kernels.ingest.kernel import grid_steps

    _, events = traced
    assert all(e[3] == {} for e in events if e[0] == "glava.ingest.dispatch")
    gs = GraphStream.open("smoke", ingest_backend="pallas")
    s, d = np.random.default_rng(1).integers(0, NODES, (2, EDGES)).astype(np.uint32)
    with jax.profiler.trace(str(tmp_path)):
        gs.ingest(s, d)
        gs.flush()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = {
        e.name: dict(e.stats)
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for e in line.events
        if e.name in ("glava.ingest.transfer", "glava.ingest.dispatch")
    }
    cfg = gs.config
    slots = spans["glava.ingest.transfer"]["slots"]
    assert spans["glava.ingest.dispatch"] == {
        "kernel_steps": grid_steps(cfg.depth, cfg.width_rows, cfg.width_cols, slots)
    }
