"""One run of one cell: set-up, the measured window, an optional traced
slice inside it, and the comparison with the plain reference.

The window drives the program's own entry (``GraphStream.ingest``) and
drains its standing subscription with ``Subscription.poll``, materialising
every answer on the host, inside three host spans of the benchmark's own:
``bench.pickup`` (waiting for and cutting the next batch), ``bench.ingest``
(the call into the entry) and ``bench.poll``.  With an every-batch
subscription each ingest call returns only after its batch has landed and
its standing queries have run, so a batch is complete when ``bench.poll``
holds its answers.

Set-up warms up from the mix's own traffic: it runs passes of the same
schedule as the window until two whole passes in a row add no compile
event, so every program the window's batches ask for is compiled or loaded
first.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import pathlib
import statistics
import time
from typing import Dict, List, Optional

import numpy as np

from bench import reference
from bench import sessions
from bench.traffic import Arrivals, Pool, Seeds, make_pool, make_queries

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = COMPILE_EVENTS[2]
# Warm-up: passes of this much schedule, until QUIET_PASSES in a row add
# no compile event.
WARM_PASS_S = 3.0
QUIET_PASSES = 2
WARM_PASSES_MAX = 12
# The traced slice starts this far into the window and lasts about this long.
TRACE_START_FRAC = 0.25
TRACE_SLICE_S = 4.0
REACH_SAMPLE_EVENTS = 12


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Compile events JAX reports (trace, lowering, backend compile), as a
    count and seconds, from its own monitoring hooks."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1
            self.backend_compiles += event == BACKEND_COMPILE

    def snapshot(self):
        return self.events, self.backend_compiles, self.seconds


@dataclasses.dataclass
class Batch:
    start: int           # first stream edge
    n: int               # edges
    t_pick: float        # schedule time the batch was cut
    t_done: float        # schedule time its answers were on the host
    ok: bool             # the entry returned and the due event arrived


@dataclasses.dataclass
class RunRecord:
    """What a run leaves for the metric readers."""

    config: Dict
    mix: Dict
    pool: Pool
    batches: List[Batch]              # window batches, in order
    traced: List[Batch]               # window batches inside the traced slice
    counters_window_start: Dict[str, int]
    counters_window_end: Dict[str, int]
    trace: Optional[object] = None    # trace.TraceSummary of the slice
    peaks: Optional[Dict] = None

    @property
    def depth(self) -> int:
        return int(self.config["sketch"]["depth"])


def require_devices(chips: int, allow_cpu: bool):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}; it does not fall back to the CPU")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices


def _quantile(values: np.ndarray, q: float) -> float:
    return float(np.quantile(values, q, method="linear"))


def run_cell(
    config: Dict,
    mix: Dict,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    chips: int = 1,
    allow_cpu: bool = False,
    trace_dir: Optional[pathlib.Path] = None,
    t_process: Optional[float] = None,
    log=print,
    pool_edges: Optional[int] = None,
) -> Dict:
    """Run one cell once and return everything the result line is built
    from.  ``allow_cpu`` is for the CPU rehearsal only."""
    t_process = time.perf_counter() if t_process is None else t_process
    import jax
    import jax.profiler as jp

    devices = require_devices(chips, allow_cpu)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Keep every program, however fast it compiled: later runs in this
    # checkout then load all of them instead of compiling the small ones.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock()
    seeds = Seeds(seed)
    graph = config["graph"]
    pool = make_pool(graph, seeds, **({} if pool_edges is None else {"pool_edges": pool_edges}))
    queries = make_queries(mix, graph, pool, seeds)
    arrivals = Arrivals(mix, seeds, seconds)
    every = int(mix["queries"].get("every", 1))
    if every != 1:
        raise ValueError("the harness times every-batch subscriptions only")
    cell = sessions.open_cell(config, pool, queries, seeds.session_seed(), every)

    ingested: List[tuple] = []          # (start, n) of every batch, warm-up included
    events: List[tuple] = []            # (batch index, answers) of every due event
    missing = 0

    def step(start: int, n: int) -> bool:
        """One batch through the entry and the poll; True when complete."""
        nonlocal missing
        try:
            with jp.TraceAnnotation("bench.ingest"):
                cell.ingest(start, n)
        except Exception as e:  # a batch that raised counts as failed
            log(f"[bench] batch at {start} raised {type(e).__name__}: {e}")
            missing += 1
            return False
        ingested.append((start, n))
        with jp.TraceAnnotation("bench.poll"):
            got = cell.poll()
        epoch = len(ingested)
        mine = [answers for e, answers in got if e == epoch]
        if mine:
            events.append((epoch - 1, mine[0]))
        else:
            missing += 1
        return bool(mine)

    def drive(cursor: int, length: float, tracer=None) -> List[Batch]:
        """The schedule from its start for ``length`` seconds, from stream
        edge ``cursor``: every batch until the first completion past it."""
        out: List[Batch] = []
        handed = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if tracer is not None:
                tracer.tick(now, out)
            with jp.TraceAnnotation("bench.pickup"):
                n, wait = arrivals.pickup(handed, now)
                while n == 0:
                    time.sleep(max(wait, 0.0))
                    now = time.perf_counter() - t0
                    n, wait = arrivals.pickup(handed, now)
            t_pick = time.perf_counter() - t0
            ok = step(cursor + handed, n)
            t_done = time.perf_counter() - t0
            out.append(Batch(cursor + handed, n, t_pick, t_done, ok))
            handed += n
            if t_done >= length:
                break
        if tracer is not None:
            tracer.tick(float("inf"), out)
        return out

    # -- warm-up: passes of the mix's own schedule until they compile nothing --
    cursor = 0
    passes: List[int] = []
    while len(passes) < WARM_PASSES_MAX:
        before = clock.events
        warm = drive(cursor, WARM_PASS_S)
        cursor += sum(b.n for b in warm)
        passes.append(clock.events - before)
        if len(passes) > QUIET_PASSES and not any(passes[-QUIET_PASSES:]):
            break
    warm_batches = len(ingested)
    warm_missing, missing = missing, 0
    warm_events = len(events)
    jax.effects_barrier()
    compiles0 = clock.snapshot()

    # -- the window ---------------------------------------------------------
    tracer = _Tracer(trace_dir, seconds) if trace else None
    counters_start = cell.counters()
    setup_s = time.perf_counter() - t_process
    batches = drive(cursor, seconds, tracer)
    cursor = batches[-1].start + batches[-1].n
    t_end = batches[-1].t_done
    compiles1 = clock.snapshot()

    peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices[:chips]
    )
    counters = cell.counters()
    missing += counters["events_dropped"]
    cell.close()
    del cell
    gc.collect()

    # -- end-to-end numbers ---------------------------------------------------
    done = [b for b in batches if b.ok]
    e2e: Dict[str, float] = {"setup_s": setup_s}
    if done:
        e2e["edges_per_s"] = sum(b.n for b in done) / done[-1].t_done
    lat_n = 0
    backlog, backlog_q = 0, []
    if arrivals.mode == "poisson":
        lat = []
        for b in batches:
            due = arrivals.due[b.start - batches[0].start : b.start - batches[0].start + b.n]
            lat.append((b.t_done if b.ok else t_end) - due)
        handed = cursor - batches[0].start
        lat.append(t_end - arrivals.due[handed : arrivals.due_before(t_end)])
        lat_all = np.concatenate(lat) * 1e3
        lat_n = int(lat_all.size)
        e2e["result_p50_ms"] = _quantile(lat_all, 0.50)
        e2e["result_p95_ms"] = _quantile(lat_all, 0.95)
        backlog = arrivals.due_before(t_end) - handed
        picks = np.array([b.t_pick for b in batches])
        handed_by = np.cumsum([b.n for b in batches])
        for q in (0.25, 0.5, 0.75, 1.0):
            t = q * t_end
            k = int(np.searchsorted(picks, t, side="right"))
            backlog_q.append(arrivals.due_before(t) - (int(handed_by[k - 1]) if k else 0))

    info = dict(
        window_s=t_end,
        batches=len(batches),
        edges=int(sum(b.n for b in batches)),
        batch_median=float(statistics.median([b.n for b in batches])),
        batch_max=int(max(b.n for b in batches)),
        tick_median_s=float(statistics.median([b.t_done - b.t_pick for b in batches])),
        backlog_edges=int(backlog),
        backlog_quarters=backlog_q,
        latency_samples=lat_n,
        warm_batches=warm_batches,
        warm_pass_compile_events=passes,
        compile_events_in_window=compiles1[0] - compiles0[0],
        backend_compiles_in_window=compiles1[1] - compiles0[1],
        compile_s_in_window=round(compiles1[2] - compiles0[2], 6),
        counters=counters,
    )
    log(f"[bench] {info}")

    record = RunRecord(
        config=config, mix=mix, pool=pool, batches=batches,
        traced=tracer.batches if tracer else [],
        counters_window_start=counters_start, counters_window_end=counters,
    )

    # -- the check ------------------------------------------------------------
    t_ref = time.perf_counter()
    hashes = reference.Hashes(config, seeds.session_seed())
    window_events = events[warm_events:]
    n_events = len(window_events)
    rng = seeds.rng("sample")
    sample = rng.choice(max(n_events, 1), size=min(REACH_SAMPLE_EVENTS, max(n_events, 1)), replace=False)
    sample = np.unique(np.append(sample, n_events - 1))
    compare = functools.partial(
        reference.compare, hashes, pool, ingested, queries, window_events,
        config["limits"], missing + warm_missing, sample,
    )
    checks = compare()
    log(f"[bench] reference check took {time.perf_counter() - t_ref:.3f}s")
    return dict(
        record=record,
        e2e=e2e,
        checks=checks,
        compare=compare,
        attempted=len(batches),
        failed=sum(not b.ok for b in batches) + counters["events_dropped"],
        device=dict(
            platform=devices[0].platform,
            kind=devices[0].device_kind,
            count=len(devices),
            memory_peak_bytes=peak,
        ),
        info=info,
    )


class _Tracer:
    """Starts the profiler TRACE_START_FRAC into the window and stops it
    after TRACE_SLICE_S (and at least two batches); keeps the batches cut
    while it ran."""

    def __init__(self, trace_dir: pathlib.Path, seconds: float):
        self.dir = trace_dir
        self.start_at = TRACE_START_FRAC * seconds
        self.length = min(TRACE_SLICE_S, 0.5 * seconds)
        self.batches: List[Batch] = []
        self.state = "before"
        self.first = 0

    def tick(self, now: float, batches: List[Batch]) -> None:
        import jax.profiler as jp

        if self.state == "before" and now >= self.start_at and now != float("inf"):
            jp.start_trace(str(self.dir), profiler_options=_profile_options())
            self.state, self.first, self.t = "on", len(batches), now
        elif self.state == "on":
            inside = batches[self.first:]
            if now == float("inf") or (now - self.t >= self.length and len(inside) >= 2):
                jp.stop_trace()
                self.batches = inside
                self.state = "done"


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
