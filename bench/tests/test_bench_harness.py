"""The harness end to end on the CPU, at the smoke sketch, and the entry's
refusal to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

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
    assert res["info"]["compile_events_in_window"] == 0
    want = {"setup_s", "edges_per_s"}
    if mix_name.endswith("poisson"):
        want |= {"result_p50_ms", "result_p95_ms"}
    assert want <= set(res["e2e"])


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
