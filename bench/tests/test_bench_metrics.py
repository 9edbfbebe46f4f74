"""Each per-layer metric reader on a fixture run."""
import importlib.util
import pathlib

import numpy as np
import pytest

from bench import harness, trace, work
from bench.traffic import Pool

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
PEAKS = {"hbm_bytes_per_s": 8.19e11}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"m_{name.replace('.', '_')}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(modules, counters_end=None):
    pool = Pool(np.array([1, 1, 2, 3], np.uint32), np.array([5, 5, 5, 6], np.uint32),
                np.ones(4, np.float32))
    batches = [harness.Batch(0, 4, 0.0, 0.1, True), harness.Batch(0, 4, 0.1, 0.2, True)]
    summary = trace.TraceSummary(window_s=0.2, busy_s=0.05, modules=modules, ops={},
                                 idle_by_span={}, devices=1)
    config = {"kind": "graphstream", "sketch": {"depth": 5}}
    start = {"closure_full": 1, "closure_incremental": 10}
    return harness.RunRecord(config, {}, pool, batches, batches, start,
                             counters_end or start, trace=summary, peaks=PEAKS)


MODULES = {"jit__update_pre(7)": 2e-3, "jit__pallas_edge_query(3)": 1e-3,
           "jit_check_heavy_keys_rel_vec(4)": 5e-4, "jit_reach_query_precomputed(9)": 5e-4,
           "jit_closure_refresh(5)": 4e-3, "jit__pallas_closure(6)": 6e-3, "jit_add(1)": 1.0}


def test_idle_share():
    assert reader("device_idle_share.sat")(_run(MODULES)) == pytest.approx(75.0)


def test_ingest_roofline_counts_the_batches_not_the_kernel():
    run = _run(MODULES)
    per_batch = work.ingest_bytes(work.batch_work(run.pool, 0, 4), 5)
    want = 100 * (2 * per_batch / 8.19e11) / 2e-3
    assert reader("ingest_roofline")(run) == pytest.approx(want)
    assert reader("ingest_roofline")(_run({"jit_add(1)": 1.0})) is None
