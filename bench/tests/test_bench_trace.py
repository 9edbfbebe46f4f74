"""The trace reduction on hand-made events, and the peaks table."""
import pathlib

import pytest

from bench import trace

ROOT = pathlib.Path(__file__).resolve().parents[2]
MS = 1e6  # ns


def _events():
    # Host: pickup 0-10 ms, ingest 10-40 ms, poll 40-50 ms.
    host = [("bench.pickup", 0.0, 10 * MS), ("bench.ingest", 10 * MS, 30 * MS),
            ("bench.poll", 40 * MS, 10 * MS)]
    ops = [("fusion", 12 * MS, 10 * MS), ("custom-call", 20 * MS, 8 * MS),  # overlap: busy 12-28
           ("gather", 42 * MS, 2 * MS), ("late", 49 * MS, 5 * MS)]           # clipped at 50
    modules = [("jit__update_pre(1)", 12 * MS, 16 * MS), ("jit_edge_query(2)", 42 * MS, 2 * MS)]
    return trace.Events({"/device:TPU:0": {trace.OPS: ops, trace.MODULES: modules}}, host)


def test_reduce_busy_window_and_gaps():
    s = trace.reduce(_events())
    assert s.window_s == pytest.approx(0.050)
    assert s.busy_s == pytest.approx((16 + 2 + 1) * 1e-3)
    assert s.idle_share == pytest.approx(1 - 19 / 50)
    assert s.idle_by_span["host:bench.pickup"] == pytest.approx(0.012)  # 0-12 ms
    # A whole gap goes to the span open at its midpoint.
    assert s.idle_by_span["host:bench.ingest"] == pytest.approx(0.014)  # 28-42 ms
    assert s.idle_by_span["host:bench.poll"] == pytest.approx(0.005)    # 44-49 ms
    assert s.module_seconds(lambda n: "_update" in n) == pytest.approx(0.016)
    top = s.breakdown()["device_ops"]
    assert top[0] == ["fusion", pytest.approx(0.010)] and len(top) <= 10


def test_reduce_averages_over_devices_and_round_trips_json():
    ev = _events()
    ev.device["/device:TPU:1"] = {trace.OPS: [("fusion", 0.0, 50 * MS)]}
    s = trace.reduce(trace.Events.from_json(ev.to_json()))
    assert s.devices == 2
    assert s.busy_s == pytest.approx((0.019 + 0.050) / 2)


def test_reduce_refuses_a_trace_without_device_work():
    with pytest.raises(ValueError):
        trace.reduce(trace.Events({}, [("bench.ingest", 0.0, MS)]))


def test_peaks_refuse_an_unknown_device(tmp_path):
    peaks = ROOT / "bench/peaks.json"
    assert trace.peaks_for("TPU v5 lite", peaks)["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(trace.UnknownDevice):
        trace.peaks_for("cpu", peaks)
    with pytest.raises(trace.UnknownDevice):
        trace.reduce_dir(tmp_path, "TPU v9 imaginary", peaks)
