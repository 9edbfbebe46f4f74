"""Readings that set the benchmark's constants, made once on the chip.

    python bench/calibrate.py readings --workload <cell> --seeds 1 2 3 ... --seconds 5
    python bench/calibrate.py sweep --workload <cell> --rates 5000 10000 ... --seconds 20
    python bench/calibrate.py spread --workload <cell> --runs 6 --seconds 20 [--seeds ...]

``readings`` runs the cell once per seed in one process and prints, per
seed, each compared number of the program and of the bfloat16 control (the
plain reference with bfloat16 counters, put in the program's place): the
lower and upper readings each limit is set between.  ``sweep`` runs an
open-loop cell at each offered rate and prints the backlog left at the
window's end, the batch sizes and the latency, from which the highest
sustained rate is read.  ``spread`` runs ``bench/run.py`` once per seed,
each in a fresh process, and prints each end-to-end metric's median and
its spreads as shares of it: the range less the run farthest from the
median (``spread``), the interquartile range of all runs (the check's
reading for a bound too loose: over eight times the wider of two sets'),
and that of the runs less the farthest (its reading for a bound too
tight), the last also as a share of the metric's bound: where the mean of
two sets' shares is over 0.5, the check refuses the bound as too tight.
All three write JSON lines under ``.bench_out/calibrate/``.
"""
from __future__ import annotations

import argparse
import ast
import gc
import json
import os
import pathlib
import statistics
import subprocess
import sys
from typing import Dict, List, Sequence

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# A run's allowance: its first run in a checkout compiles.
RUN_TIMEOUT_S = 1300
INFO_PREFIX = "[bench] "


def less_farthest(values: Sequence[float]) -> List[float]:
    """The runs of a set, sorted, less the one farthest from their median
    (of three or more): one far-off run in a set does no harm, two do."""
    values = sorted(values)
    if len(values) < 3:
        return values
    med = statistics.median(values)
    values.remove(max(values, key=lambda v: abs(v - med)))
    return values


def spread(values: Sequence[float]) -> float:
    """The range of a set of runs less the one farthest from their median:
    never wider than the whole range."""
    rest = less_farthest(values)
    return rest[-1] - rest[0]


def iqr(values: Sequence[float]) -> float:
    """The distance between the first and third quartiles, as
    ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def summarise(values: Sequence[float], bound: float) -> Dict[str, float]:
    """A set's median and spreads, the last three as shares of the median
    (the quartiles of fewer than two runs are nought), and the
    interquartile range less the farthest run as a share of the bound."""
    med = statistics.median(values)
    rest = less_farthest(values)
    out = dict(runs=len(values), median=med, spread=spread(values),
               spread_share=spread(values) / med,
               iqr_share=iqr(values) / med if len(values) > 1 else 0.0,
               iqr_less_far_share=iqr(rest) / med if len(rest) > 1 else 0.0)
    out["share_of_bound"] = out["iqr_less_far_share"] / bound
    return out


def spread_seeds(runs: int) -> List[int]:
    """``spread``'s seeds when none are given: distinct and past 32 signed
    bits, as the benchmark's check draws them."""
    return [2**31 + 7919 * (i + 1) for i in range(runs)]


def run_once(workload: str, seed: int, seconds: float, trace: int) -> Dict:
    """One ``bench/run.py`` run in a fresh process: its result line and its
    ``[bench]`` info log."""
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=RUN_TIMEOUT_S,
    )
    row = dict(seed=seed, rc=proc.returncode)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and lines:
        row["result"] = json.loads(lines[-1])
    else:
        row["stderr_tail"] = proc.stderr[-2000:]
    for line in proc.stderr.splitlines():
        if line.startswith(INFO_PREFIX + "{"):
            row["info"] = ast.literal_eval(line[len(INFO_PREFIX):])
    return row


def spread_rows(rows: List[Dict], end_to_end: List[Dict]) -> Dict[str, Dict]:
    """``summarise`` of each end-to-end metric the rows report."""
    out = {}
    for m in end_to_end:
        vals = [r["result"]["metrics"][m["name"]]["value"] for r in rows
                if "result" in r and m["name"] in r["result"]["metrics"]]
        if vals:
            out[m["name"]] = summarise(vals, float(m["bound"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep", "spread"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=None)
    ap.add_argument("--runs", type=int, default=6, help="spread: seeds to derive when --seeds is not given")
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    if args.seeds is None:
        args.seeds = spread_seeds(args.runs) if args.mode == "spread" else [1]

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    out = ROOT / ".bench_out" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    sink = open(out / f"{args.mode}-{args.workload}.jsonl", "a")
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731

    def emit(row):
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    if args.mode == "spread":
        # This process stays off JAX: each run's own process holds the chip.
        rows = []
        for seed in args.seeds:
            rows.append(run_once(args.workload, seed, args.seconds, 0))
            emit(rows[-1])
        e2e = [m for m in bench["end_to_end"]
               if "workloads" not in m or args.workload in m["workloads"]]
        emit(dict(workload=args.workload, seconds=args.seconds, seeds=args.seeds,
                  failed_runs=sum("result" not in r for r in rows),
                  incorrect_runs=sum(not r.get("result", {}).get("correct", False) for r in rows),
                  spread=spread_rows(rows, e2e)))
        sink.close()
        return 0
    from bench import harness

    if args.mode == "readings":
        for seed in args.seeds:
            res = harness.run_cell(config, mix, seed, args.seconds, False,
                                   chips=int(cell["chips"]), log=log)
            control = res["compare"](control=True)
            emit(dict(
                seed=seed,
                program={c.name: c.value for c in res["checks"]},
                control={c.name: c.value for c in control},
                e2e=res["e2e"], info=res["info"], attempted=res["attempted"],
                failed=res["failed"], device=res["device"],
            ))
            del res, control
            gc.collect()
    else:
        for rate in args.rates:
            mix["arrivals"]["rate"] = rate
            res = harness.run_cell(config, mix, args.seeds[0], args.seconds, False,
                                   chips=int(cell["chips"]), log=log)
            emit(dict(
                rate=rate, e2e=res["e2e"], info=res["info"],
                checks={c.name: c.value for c in res["checks"]},
            ))
            del res
            gc.collect()
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
