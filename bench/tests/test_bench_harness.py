"""The harness end to end on the CPU, at the smoke sketch, and the entry's
refusal to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [
    ("graph500-s26", "alarms-sat"),
    ("graph500-s26", "reach-poisson"),
    ("graph500-s26", "alarms-poisson"),
]


@pytest.mark.parametrize("config_name,mix_name", CELLS)
def test_cell_runs_correct_on_cpu(run_smoke, config_name, mix_name):
    res = run_smoke(config_name, mix_name)
    bad = [c for c in res["checks"] if not c.ok]
    assert not bad, bad
    assert res["attempted"] > 0 and res["failed"] == 0
    # A CPU run names its device: no number of it can pass for a chip's.
    assert res["device"]["platform"] == "cpu"
    info = res["info"]
    assert info["compile_events_in_window"] == 0
    # What the host did in the window, logged beside the numbers.
    assert list(info["tick_ms"]) == ["p50", "p90", "p99", "max"]
    assert 0 < info["tick_ms"]["p50"] <= info["tick_ms"]["p90"] <= info["tick_ms"]["p99"] <= info["tick_ms"]["max"]
    assert set(info["pickup_ms"]) == {"p50", "max", "sum"}
    assert len(info["quarter_edges_per_s"]) == 4
    assert sum(info["quarter_edges_per_s"]) / 4 == pytest.approx(res["e2e"]["edges_per_s"], rel=1e-6)
    assert len(info["gc_collections"]) == 3 and info["gc_pause_ms"] >= 0
    assert info["cpu_user_s"] > 0 and info["cpu_sys_s"] >= 0
    assert info["ctx_voluntary"] >= 0 and info["ctx_involuntary"] >= 0
    assert info["main_thread_cpu_s"] > 0
    assert set(info["stalls"]) == {"n", "excess_ms"}
    want = {"setup_s", "edges_per_s"}
    if mix_name.endswith("poisson"):
        want |= {"result_p50_ms", "result_p95_ms"}
    assert want <= set(res["e2e"])


def _window(*rows):
    return [harness.Batch(start, n, t_pick, t_done, ok) for start, n, t_pick, t_done, ok in rows]


def test_edges_per_s_over_a_recorded_window():
    batches = _window(
        (0, 65536, 0.000, 0.057, True),
        (65536, 65536, 0.057, 0.121, False),   # raised or lost its event
        (131072, 65536, 0.121, 0.176, True),
        (196608, 65536, 0.176, 0.240, True),
        (262144, 65536, 0.240, 0.298, False),  # the last batch, failed
    )
    # Completed edges over the time from the window's start to the last
    # completion: failed batches add no edges but their time still counts.
    assert harness.edges_per_s(batches) == 3 * 65536 / 0.240
    assert harness.edges_per_s(_window((0, 10, 0.0, 0.1, False))) is None


def test_window_stats_of_a_recorded_window():
    batches = _window(
        (0, 100, 0.01, 0.05, True),
        (100, 100, 0.06, 0.09, True),
        (200, 100, 0.10, 0.15, True),
        (300, 100, 0.20, 0.22, False),
        (400, 100, 0.22, 0.40, True),
    )
    stats = harness.window_stats(batches)
    assert stats["tick_ms"]["max"] == pytest.approx(180.0)
    assert stats["tick_ms"]["p50"] == pytest.approx(40.0)
    # One tick over twice the median (180 ms > 80 ms), 140 ms past the median.
    assert stats["stalls"] == pytest.approx({"n": 1, "excess_ms": 140.0})
    # Pick-up: from the window's start or the last completion to the cut.
    assert stats["pickup_ms"] == pytest.approx({"p50": 10.0, "max": 50.0, "sum": 80.0})
    # Quarters of the 0.40 s span: completions at 0.05, 0.09 | 0.15 | - | 0.40.
    assert stats["quarter_edges_per_s"] == pytest.approx([2000.0, 1000.0, 0.0, 1000.0])
    assert sum(stats["quarter_edges_per_s"]) / 4 == pytest.approx(harness.edges_per_s(batches))


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500.alarms.sat",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_entry_refuses_without_tpu():
    proc = _run_entry(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_entry_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_entry(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_file_names_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
