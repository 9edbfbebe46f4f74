"""The web-share cell: its configuration rehearsed at a smoke sketch on four
CPU devices (the program's placement rule told the devices are small, so
the session row-shards as on a four-chip host), and its per-layer readers
on a fixture run of four chips."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import harness, trace, work
from bench.traffic import Pool

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "bench" / "metrics"
CELL = "web-share.alarms.sat"

_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [".", "src"]
    from bench import harness
    from bench.tests.conftest import SMOKE_POOL, smoke
    import repro.api.stream as stream

    meshes = []
    init = stream.GraphStream.__init__
    def recording_init(self, *a, **kw):
        init(self, *a, **kw)
        meshes.append(None if self._mesh is None else dict(self._mesh.shape))
    stream.GraphStream.__init__ = recording_init
    stream._bytes_limit = lambda device: 4 << 20   # 4 MB of counters do not fit

    config, mix = smoke("graph500-s29-web-share", "alarms-sat")
    config["sketch"].update(depth=2, width_rows=1024, width_cols=1024)
    res = harness.run_cell(config, mix, 2**31 + 11, 1.5, False, chips=4, allow_cpu=True,
                           pool_edges=SMOKE_POOL, log=lambda m: None)
    print("RESULT " + json.dumps(dict(
        meshes=meshes, devices=res["device"]["count"], attempted=res["attempted"],
        failed=res["failed"], bad=[c.name for c in res["checks"] if not c.ok])))
    """
)


def test_config_is_the_cells_and_states_its_cut():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 4
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    sk = config["sketch"]
    assert (sk["depth"], sk["width_rows"], sk["width_cols"]) == (2, 65536, 65536)
    assert config["device_state_bytes"]["counters"] == 4 * sk["depth"] * sk["width_rows"] * sk["width_cols"]
    assert config["device_state_bytes"]["counters_per_chip"] * 4 == config["device_state_bytes"]["counters"]
    assert entry["reduced"] == ["sketch.depth"] and config["reduced"][0].startswith("sketch.depth 4 -> 2")


def test_cell_runs_correct_on_four_cpu_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
                          env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["meshes"] == [{"data": 1, "model": 4}] and res["devices"] == 4, res
    assert res["attempted"] > 0 and res["failed"] == 0 and res["bad"] == [], res


def reader(name):
    spec = importlib.util.spec_from_file_location(f"m_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


OPS = {
    "%ingest_pallas.1 = f32[2,16384,65536]{2,1,0:T(8,128)} custom-call(%a, %b)": 30e-3,
    "%pmin.7 = f32[2,1024]{1,0:T(2,128)S(1)} all-reduce(%and_select_fusion), channel_id=1": 2e-6,
    "%fusion.3 = f32[2,1024]{1,0} fusion(%pmin.7, %all-gather.2), kind=kLoop": 1e-3,
    "%all-gather-start.2 = (f32[2]{0}, f32[8]{0}) all-gather-start(%p)": 4e-6,
}
MODULES = {"jit__update_mesh(7)": 32e-3, "jit_glava_query_edge(3)": 1e-3,
           "jit_glava_query_in_flow(4)": 2e-4, "jit_add(1)": 1.0}


def _run(ops=OPS, modules=MODULES):
    pool = Pool(np.array([1, 1, 2, 3], np.uint32), np.array([5, 5, 5, 6], np.uint32),
                np.ones(4, np.float32))
    batches = [harness.Batch(0, 4, 0.0, 0.1, True), harness.Batch(0, 4, 0.1, 0.2, True)]
    summary = trace.TraceSummary(window_s=0.2, busy_s=0.05, modules=modules, ops=ops,
                                 idle_by_span={}, devices=4)
    config = {"kind": "graphstream", "sketch": {"depth": 2}}
    return harness.RunRecord(config, {}, pool, batches, batches, {}, {}, trace=summary,
                             peaks={"hbm_bytes_per_s": 8.19e11})


def test_readers_on_four_chips():
    run = _run()
    assert reader("device_idle_share.web")(run) == pytest.approx(75.0)
    per_batch = work.ingest_bytes(work.batch_work(run.pool, 0, 4), 2)
    assert reader("ingest_roofline.web")(run) == pytest.approx(100 * (2 * per_batch / (4 * 8.19e11)) / 32e-3)
    assert reader("query_device_ms.web")(run) == pytest.approx(0.6)
    # The pmin and the gather's start count; a fusion that reads them does not.
    assert reader("collective_ms.web")(run) == pytest.approx(1e3 * 6e-6 / 2)


def test_readers_find_nothing_without_their_programs():
    run = _run(ops={}, modules={"jit_add(1)": 1.0})
    assert reader("ingest_roofline.web")(run) is None
    assert reader("query_device_ms.web")(run) is None
    assert reader("collective_ms.web")(run) == 0.0
    run.trace = None
    assert all(reader(m)(run) is None for m in
               ("device_idle_share.web", "ingest_roofline.web", "query_device_ms.web",
                "collective_ms.web", "idle_ingest_ms.web"))
