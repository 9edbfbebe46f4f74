"""``bench/calibrate.py spread``: its spread arithmetic and how it reads a run."""
import json
import statistics
import subprocess

import pytest

from bench import calibrate


@pytest.mark.parametrize("values,want", [
    # The farthest run from the median (130) is left out: the range of the rest.
    ([100, 101, 102, 103, 104, 130], 4),
    # The farthest run can lie below the median.
    ([70, 100, 101, 102, 103, 104], 4),
    # A tie for farthest leaves out one of the two; the other still counts.
    ([90, 100, 100, 100, 100, 110], 10),
    # Runs that all agree spread by nothing.
    ([5.0] * 6, 0.0),
    # Two runs are too few to leave one out.
    ([1.0, 3.0], 2.0),
])
def test_spread_leaves_out_the_farthest_run(values, want):
    assert calibrate.spread(values) == pytest.approx(want)


def test_spread_never_exceeds_the_range():
    values = [1_141_700.0, 1_139_000.0, 1_150_200.0, 1_143_100.0, 1_138_400.0, 1_144_900.0]
    assert calibrate.spread(values) <= max(values) - min(values)
    # 1,150,200 is farthest from the median 1,142,400: the rest span 6,500.
    assert calibrate.spread(values) == pytest.approx(1_144_900.0 - 1_138_400.0)


def test_summarise_shares_of_median_and_bound():
    values = [100.0, 100.2, 100.4, 100.6, 100.8, 103.0]
    out = calibrate.summarise(values, bound=0.01)
    med = statistics.median(values)
    assert out["runs"] == 6 and out["median"] == med
    assert out["spread"] == pytest.approx(0.8)
    assert out["spread_share"] == pytest.approx(0.8 / med)
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert out["iqr_share"] == pytest.approx((q3 - q1) / med)
    # Less the farthest run (103.0), the quartiles of the other five are
    # 100.1 and 100.7: the reading a bound is held to as too tight.
    assert out["iqr_less_far_share"] == pytest.approx(0.6 / med)
    assert out["share_of_bound"] == pytest.approx(0.6 / med / 0.01)


def test_summarise_of_too_few_runs():
    out = calibrate.summarise([10.0], bound=0.02)
    assert out["spread"] == 0.0 and out["iqr_share"] == 0.0 and out["share_of_bound"] == 0.0
    out = calibrate.summarise([10.0, 12.0], bound=0.02)
    assert out["spread"] == 2.0 and out["iqr_less_far_share"] > 0


def test_spread_rows_reads_each_metric_of_the_runs_that_finished():
    def row(seed, rate, setup):
        metrics = {"edges_per_s": {"value": rate, "unit": "edges/s"},
                   "setup_s": {"value": setup, "unit": "s"}}
        return dict(seed=seed, rc=0, result={"correct": True, "metrics": metrics})

    rows = [row(1, 10.0, 20.0), row(2, 11.0, 21.0), row(3, 12.0, 22.0),
            dict(seed=4, rc=1, stderr_tail="boom")]
    e2e = [{"name": "edges_per_s", "bound": 0.01}, {"name": "setup_s", "bound": 0.25}]
    out = calibrate.spread_rows(rows, e2e)
    assert out["edges_per_s"]["runs"] == 3 and out["edges_per_s"]["median"] == 11.0
    assert out["setup_s"]["median"] == 21.0


def test_run_once_reads_the_result_line_and_the_info_log(monkeypatch):
    line = {"correct": True, "metrics": {"edges_per_s": {"value": 5.0, "unit": "edges/s"}}}
    done = subprocess.CompletedProcess(
        args=[], returncode=0, stdout="noise\n" + json.dumps(line) + "\n",
        stderr="[bench] {'tick_ms': {'p50': 57.1}, 'stalls': {'n': 1}}\ncheck x 0 limit 0 ok\n",
    )
    monkeypatch.setattr(calibrate.subprocess, "run", lambda *a, **kw: done)
    row = calibrate.run_once("g500.alarms.sat", 7, 20.0, 0)
    assert row == {"seed": 7, "rc": 0, "result": line,
                   "info": {"tick_ms": {"p50": 57.1}, "stalls": {"n": 1}}}
    failed = subprocess.CompletedProcess(args=[], returncode=1, stdout="", stderr="no TPU")
    monkeypatch.setattr(calibrate.subprocess, "run", lambda *a, **kw: failed)
    assert calibrate.run_once("g500.alarms.sat", 7, 20.0, 0) == {"seed": 7, "rc": 1, "stderr_tail": "no TPU"}


def test_only_spread_derives_its_seeds(monkeypatch):
    # ``spread`` with no --seeds runs --runs distinct seeds past 32 signed bits.
    seen = []
    line = {"correct": True, "metrics": {"edges_per_s": {"value": 5.0, "unit": "edges/s"}}}
    monkeypatch.setattr(calibrate, "run_once",
                        lambda w, seed, s, t: seen.append(seed) or dict(seed=seed, rc=0, result=line))
    assert calibrate.main(["spread", "--workload", "g500.alarms.sat", "--runs", "3", "--seconds", "1"]) == 0
    assert seen == calibrate.spread_seeds(3)
    assert len(set(seen)) == 3 and min(seen) > 2**31

    # ``readings`` and ``sweep`` keep their one default seed, 1.
    from bench import harness

    seen.clear()

    def run_cell(config, mix, seed, *a, **kw):
        seen.append(seed)
        return dict(compare=lambda control: [], checks=[], e2e={}, info={}, attempted=0,
                    failed=0, device={})

    monkeypatch.setattr(harness, "run_cell", run_cell)
    assert calibrate.main(["readings", "--workload", "g500.alarms.sat", "--seconds", "1"]) == 0
    assert calibrate.main(["sweep", "--workload", "g500.alarms.sat", "--rates", "10", "--seconds", "1"]) == 0
    assert seen == [1, 1]
