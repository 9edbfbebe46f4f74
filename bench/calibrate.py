"""Readings that set the benchmark's constants, made once on the chip.

    python bench/calibrate.py readings --workload <cell> --seeds 1 2 3 ... --seconds 5
    python bench/calibrate.py sweep --workload <cell> --rates 5000 10000 ... --seconds 20

``readings`` runs the cell once per seed in one process and prints, per
seed, each compared number of the program and of the bfloat16 control (the
plain reference with bfloat16 counters, put in the program's place): the
lower and upper readings each limit is set between.  ``sweep`` runs an
open-loop cell at each offered rate and prints the backlog left at the
window's end, the batch sizes and the latency, from which the highest
sustained rate is read.  Both write JSON lines under ``.bench_out/calibrate/``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[1])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

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
