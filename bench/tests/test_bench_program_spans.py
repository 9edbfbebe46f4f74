"""The attribution of idle device time to the program's spans, the search
for a run's trace, and the three readers built on them."""
import importlib.util
import pathlib

import numpy as np
import pytest

from bench import harness, program_spans, trace
from bench.program_spans import Span
from bench.traffic import Pool

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
US = 1e3  # ns per microsecond


def reader(name):
    spec = importlib.util.spec_from_file_location(f"m_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _events():
    """One batch on a 100 us slice: the benchmark's spans bound it, the
    program's nest inside, and the device runs four operations."""
    ops = [("op", s * US, (e - s) * US) for s, e in [(20, 25), (40, 45), (58, 59), (80, 85)]]
    host = [("bench.ingest", 0.0, 95 * US), ("bench.poll", 95 * US, 5 * US)]
    ev = trace.Events(
        device={"/device:TPU:0": {trace.OPS: ops},
                "/device:TPU:1": {trace.OPS: [("op", 500 * US, US)]}},  # outside the slice
        host=host,
    )
    spans = [Span(n, s * US, e * US, 1) for n, s, e in [
        ("bench.ingest", 0, 95), ("bench.poll", 95, 100),
        ("glava.ingest", 5, 95), ("glava.ingest.preagg", 10, 30),
        ("glava.tick", 50, 90), ("glava.tick.plan", 55, 70), ("glava.query.edge", 56, 60),
        ("glava.ingest", 120, 130),  # starts after the slice: not a batch of it
    ]]
    return ev, program_spans.nest(spans)


def test_nest_gives_each_span_its_innermost_container():
    _, spans = _events()
    parent = {sp.name: spans[sp.parent].name if sp.parent is not None else None
              for sp in spans if sp.start < 100 * US}
    assert parent["glava.ingest"] == "bench.ingest"
    assert parent["glava.ingest.preagg"] == "glava.ingest"
    assert parent["glava.tick"] == "glava.ingest"
    assert parent["glava.query.edge"] == "glava.tick.plan"
    assert parent["bench.poll"] is None


def test_idle_goes_to_the_innermost_open_span_and_the_parts_sum_to_the_slice():
    ev, spans = _events()
    a = program_spans.attribute(ev, spans)
    got = {name: round(s * 1e6, 9) for name, s in a.table()}
    assert got == {"glava.ingest": 25.0, "glava.tick": 20.0, "glava.ingest.preagg": 15.0,
                   "glava.tick.plan": 11.0, "bench.ingest": 5.0, "bench.poll": 5.0,
                   "glava.query.edge": 3.0}
    summary = trace.reduce(ev)
    assert a.window_s == summary.window_s
    assert a.idle_s == pytest.approx(summary.window_s - summary.busy_s, abs=1e-15)
    assert a.idle_ingest_s == pytest.approx(40e-6)
    assert a.idle_tick_s == pytest.approx(34e-6)
    assert a.batches == 1


def test_a_slice_with_no_span_open_goes_to_none():
    ev, _ = _events()
    a = program_spans.attribute(ev, [])
    assert [name for name, _ in a.table()] == [program_spans.NONE]
    assert a.batches == 0


def _cpu_trace(root: pathlib.Path) -> float:
    """A real profiler trace under ``root`` holding the benchmark's spans;
    returns the slice they bound, in seconds."""
    import jax.profiler as jp

    jp.start_trace(str(root))
    for name in trace.HOST_SPANS:
        with jp.TraceAnnotation(name):
            pass
    jp.stop_trace()
    ev = trace.load(next(root.rglob("*.xplane.pb")))
    lo = min(s for _, s, _ in ev.host)
    hi = max(s + d for _, s, d in ev.host)
    return (hi - lo) * 1e-9


def test_locate_takes_the_trace_only_when_its_slice_matches(tmp_path):
    window_s = _cpu_trace(tmp_path)
    path = program_spans.locate(window_s, tmp_path)
    assert path is not None and path.suffix == ".pb"
    assert program_spans.locate(window_s + 2e-9, tmp_path) is None
    assert program_spans.locate(window_s, tmp_path / "empty") is None


PEAKS = {"hbm_bytes_per_s": 8.19e11}


def _run(modules):
    pool = Pool(np.array([1, 2], np.uint32), np.array([3, 4], np.uint32), np.ones(2, np.float32))
    batches = [harness.Batch(0, 2, 0.0, 0.1, True), harness.Batch(0, 2, 0.1, 0.2, True)]
    summary = trace.TraceSummary(window_s=100e-6, busy_s=16e-6, modules=modules, ops={},
                                 idle_by_span={}, devices=1)
    config = {"kind": "graphstream", "sketch": {"depth": 5}}
    return harness.RunRecord(config, {}, pool, batches, batches, {}, {}, trace=summary, peaks=PEAKS)


MODULES = {"jit__update_pre(7)": 2e-3, "jit_glava_query_edge(3)": 3e-3,
           "jit_glava_query_heavy_rel_vec(4)": 5e-4, "jit_glava_query_pad(5)": 5e-4,
           "jit_add(1)": 1.0}


def test_query_device_ms_counts_the_named_query_programs_per_batch():
    assert reader("query_device_ms.sat")(_run(MODULES)) == pytest.approx(2.0)
    assert reader("query_device_ms.sat")(_run({"jit__pallas_edge_query(3)": 1e-3})) is None


def test_ingest_roofline_and_query_device_ms_split_the_programs():
    ingest = reader("ingest_roofline").__globals__["is_ingest"]
    query = reader("query_device_ms.sat").__globals__["is_query"]
    assert [m for m in MODULES if ingest(m)] == ["jit__update_pre(7)"]
    assert [m for m in MODULES if query(m)] == [m for m in MODULES if "glava_query_" in m]


def test_idle_readers_read_the_attribution(monkeypatch):
    ev, spans = _events()
    monkeypatch.setattr(program_spans, "for_run", lambda run: program_spans.attribute(ev, spans))
    run = _run(MODULES)
    assert reader("idle_ingest_ms.sat")(run) == pytest.approx(40e-3)
    assert reader("idle_tick_ms.sat")(run) == pytest.approx(34e-3)
    monkeypatch.setattr(program_spans, "for_run", lambda run: None)
    assert reader("idle_ingest_ms.sat")(run) is None
    assert reader("idle_tick_ms.sat")(run) is None
