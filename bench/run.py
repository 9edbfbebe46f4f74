"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``) and its
traffic mix (``bench/traffic/<mix>.json``) are found by name through
``BENCHMARK.json``; per-layer metrics by name in ``bench/metrics/``.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.
The same numbers are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def applies(spec: dict, cell: str, reported: set) -> bool:
    """A metric applies to a cell it lists, or, without a list, to every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in spec:
        return cell in spec["workloads"]
    return spec.get("moves") in reported if "moves" in spec else True


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(args, log, t_process: float = T_PROCESS) -> dict:
    """Run the cell once and return its result line (``harness.NoChip``
    when there is no chip, ``LookupError`` for an unknown cell)."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise LookupError(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    from bench import harness

    trace_dir = OUT / "trace" / f"{args.workload}-{args.seed}"
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    res = harness.run_cell(
        config, mix, args.seed, args.seconds, bool(args.trace),
        chips=int(cell["chips"]), trace_dir=trace_dir, t_process=t_process, log=log,
    )
    e2e_specs = [m for m in bench["end_to_end"] if applies(m, args.workload, set())]
    reported = {m["name"] for m in e2e_specs}
    metrics = {}
    breakdown = None
    device = dict(res["device"])
    if not args.trace:
        for m in e2e_specs:
            if m["name"] in res["e2e"]:
                metrics[m["name"]] = {"value": res["e2e"][m["name"]], "unit": m["unit"]}
    else:
        from bench import trace as trace_mod

        record = res["record"]
        record.trace = trace_mod.reduce_dir(trace_dir, device["kind"], BENCH / "peaks.json")
        record.peaks = trace_mod.peaks_for(device["kind"], BENCH / "peaks.json")
        device["busy_s"] = record.trace.busy_s
        device["window_s"] = record.trace.window_s
        breakdown = record.trace.breakdown()
        for m in bench["per_layer"]:
            if not applies(m, args.workload, reported):
                continue
            value = load_reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = res["checks"]
    line = {
        "correct": all(c.ok for c in checks),
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAIL'}")
    return line


def main(argv=None) -> int:
    args = parse(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log under /tmp
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not in this checkout ({e})", file=sys.stderr)
        return 2
    from bench import harness

    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        line = result_line(args, log)
    except (harness.NoChip, LookupError, FileNotFoundError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1 if isinstance(e, harness.NoChip) else 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
