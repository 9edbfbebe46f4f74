"""The program's own spans in a run's trace, and the device's idle time put
down to them.

The program opens ``glava.*`` spans (``jax.profiler.TraceAnnotation``)
around its host work: ``glava.ingest`` and its children for one batch,
``glava.tick`` for the standing-query tick inside it, ``glava.query.<family>``
for one query family's dispatch and fetch.  They land in the same
``.xplane.pb`` as the device planes, on the same clock, so every idle device
nanosecond of the traced slice is put down to the innermost span open at
that nanosecond: an interval sweep over the span and idle boundaries, per
device, averaged over the devices that ran anything, as ``bench/trace.py``
averages busy time.  Idle time outside every program span goes to the
innermost benchmark span open then (``bench.poll``, ...), else to
``(none)``.  The parts add up to the slice's idle time.

The metric readers call ``for_run``: it finds the run's trace, the newest
``.xplane.pb`` under ``.bench_out/trace/``, accepted only if its benchmark
spans give exactly the slice the run's ``TraceSummary`` was reduced over,
and attributes it once per process.  A trace without the program's spans
gives None.

    python3 bench/program_spans.py <file.xplane.pb>

prints the attribution table and every program span with its stats.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import pathlib
import sys
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PREFIX = "glava."
INGEST, TICK = "glava.ingest", "glava.tick"
NONE = "(none)"
TRACE_ROOT = pathlib.Path(__file__).resolve().parents[1] / ".bench_out" / "trace"


@dataclasses.dataclass
class Span:
    """One host span: a program span (``glava.*``) or a benchmark span."""

    name: str
    start: float                  # ns, the profiler's clock
    end: float
    thread: int                   # the host line it was recorded on
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)
    parent: Optional[int] = None  # index of the enclosing span on its thread
    depth: int = 0


def nest(spans: List[Span]) -> List[Span]:
    """Sort the spans and give each its parent and depth: the innermost span
    of its own thread that contains it."""
    spans = sorted(spans, key=lambda s: (s.thread, s.start, -s.end))
    stack: List[int] = []
    for i, sp in enumerate(spans):
        while stack and not (
            spans[stack[-1]].thread == sp.thread and sp.end <= spans[stack[-1]].end
        ):
            stack.pop()
        sp.parent = stack[-1] if stack else None
        sp.depth = len(stack)
        stack.append(i)
    return spans


def load_spans(path: pathlib.Path) -> List[Span]:
    """The program's and the benchmark's host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    thread = 0
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(PREFIX) or e.name in trace.HOST_SPANS:
                    start = float(e.start_ns)
                    out.append(Span(e.name, start, start + float(e.duration_ns), thread, dict(e.stats)))
    return nest(out)


def _slice(ev: trace.Events) -> Tuple[float, float]:
    """The traced slice, from the benchmark's spans, as ``trace.reduce``
    takes it."""
    if not ev.host:
        raise ValueError("the trace holds no benchmark span (bench.*)")
    return min(s for _, s, _ in ev.host), max(s + d for _, s, d in ev.host)


def _busy(lines, lo: float, hi: float) -> List[Tuple[float, float]]:
    """A device's busy intervals in the slice, as ``trace.reduce`` takes them."""
    base = lines.get(trace.OPS) or lines.get(trace.MODULES) or []
    return trace._union(trace._clip([(s, s + d) for _, s, d in base], lo, hi))


def _sweep(gaps, spans: List[Span], lo: float, hi: float) -> Dict[int, float]:
    """Idle ns by innermost open span (index; -1 for none)."""
    points = []
    for i, sp in enumerate(spans):
        s, e = max(sp.start, lo), min(sp.end, hi)
        if e > s:
            points += [(s, 1, i), (e, -1, i)]
    for s, e in gaps:
        points += [(s, 1, -1), (e, -1, -1)]
    points.sort(key=lambda p: p[0])
    out: Dict[int, float] = collections.defaultdict(float)
    open_spans: set = set()
    idle = 0
    prev = None
    for t, step, i in points:
        if idle and t > prev:
            inner = max(open_spans, key=lambda j: (spans[j].depth, spans[j].start), default=-1)
            out[inner] += t - prev
        if i < 0:
            idle += step
        elif step > 0:
            open_spans.add(i)
        else:
            open_spans.discard(i)
        prev = t
    return out


@dataclasses.dataclass
class Attribution:
    """The slice's idle device time, put down to the spans open in it."""

    window_s: float
    idle_s: float
    spans: List[Span]
    idle_by_span: Dict[int, float]   # seconds by span index; -1 outside every span
    lo: float
    hi: float

    def chain(self, i: int) -> List[str]:
        """The names of span ``i`` and of the spans that enclose it."""
        names = []
        while i is not None and i >= 0:
            names.append(self.spans[i].name)
            i = self.spans[i].parent
        return names

    def idle_under(self, name: str, excluding: Optional[str] = None) -> float:
        """Idle seconds while ``name`` or a span inside it was the innermost
        span open, leaving out those inside ``excluding``."""
        total = 0.0
        for i, s in self.idle_by_span.items():
            names = self.chain(i)
            if name in names and (excluding is None or excluding not in names):
                total += s
        return total

    @property
    def batches(self) -> int:
        """``glava.ingest`` spans that start inside the slice."""
        return sum(sp.name == INGEST and self.lo <= sp.start < self.hi for sp in self.spans)

    @property
    def idle_ingest_s(self) -> float:
        return self.idle_under(INGEST, excluding=TICK)

    @property
    def idle_tick_s(self) -> float:
        return self.idle_under(TICK)

    def table(self) -> List[Tuple[str, float]]:
        """Idle seconds by the innermost span's name, largest first."""
        by_name: Dict[str, float] = collections.defaultdict(float)
        for i, s in self.idle_by_span.items():
            by_name[self.spans[i].name if i >= 0 else NONE] += s
        return sorted(by_name.items(), key=lambda kv: -kv[1])


def attribute(ev: trace.Events, spans: List[Span]) -> Attribution:
    """Put every idle device nanosecond of the slice down to a span."""
    lo, hi = _slice(ev)
    total: Dict[int, float] = collections.defaultdict(float)
    used = 0
    for lines in ev.device.values():
        busy = _busy(lines, lo, hi)
        if not busy:
            continue  # this device ran nothing in the slice
        used += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for i, ns in _sweep(gaps, spans, lo, hi).items():
            total[i] += ns
    if used == 0:
        raise ValueError("no operation ran on a device inside the traced slice")
    by_span = {i: ns * 1e-9 / used for i, ns in total.items()}
    return Attribution((hi - lo) * 1e-9, sum(by_span.values()), spans, by_span, lo, hi)


@functools.lru_cache(maxsize=4)
def _events(path: str) -> trace.Events:
    return trace.load(pathlib.Path(path))


@functools.lru_cache(maxsize=4)
def _attribution(path: str) -> Attribution:
    return attribute(_events(path), load_spans(pathlib.Path(path)))


def locate(window_s: float, root: pathlib.Path = TRACE_ROOT) -> Optional[pathlib.Path]:
    """The newest ``.xplane.pb`` under ``root``, if its benchmark spans give
    ``window_s`` to the nanosecond; else None."""
    files = sorted(pathlib.Path(root).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime_ns)
    if not files:
        return None
    ev = _events(str(files[-1]))
    if not ev.host:
        return None  # not a benchmark run's trace
    lo, hi = _slice(ev)
    return files[-1] if abs((hi - lo) - window_s * 1e9) < 0.5 else None


def for_run(run, root: pathlib.Path = TRACE_ROOT) -> Optional[Attribution]:
    """The attribution of the run's traced slice, or None where the trace
    cannot be found or holds no ``glava.ingest`` span."""
    if run.trace is None:
        return None
    path = locate(run.trace.window_s, root)
    if path is None:
        return None
    a = _attribution(str(path))
    return a if a.batches else None


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python3 bench/program_spans.py <file.xplane.pb>", file=sys.stderr)
        return 2
    path = args[0]
    a = _attribution(path)
    summary = trace.reduce(_events(path))
    n = a.batches
    print(f"slice {a.window_s:.9f} s, {n} batches (glava.ingest spans starting in it), {summary.devices} device(s)")
    print(f"{'innermost span':<28} {'idle ms':>12} {'ms/batch':>10}")
    for name, s in a.table():
        print(f"{name:<28} {s * 1e3:12.6f} {s * 1e3 / max(n, 1):10.6f}")
    inside = sum(s for i, s in a.idle_by_span.items() if i >= 0 and a.spans[i].name.startswith(PREFIX))
    want = summary.window_s - summary.busy_s
    print(f"idle under glava.* {inside:.9f} s + outside {a.idle_s - inside:.9f} s = {a.idle_s:.9f} s; "
          f"slice x idle share {want:.9f} s; difference {abs(a.idle_s - want) * 1e9:.3f} ns")
    print(f"per batch: idle_ingest_ms {a.idle_ingest_s * 1e3 / max(n, 1):.6f}, "
          f"idle_tick_ms {a.idle_tick_s * 1e3 / max(n, 1):.6f}")
    print("program spans in the slice (ms, stats):")
    for i, sp in enumerate(a.spans):
        if sp.name.startswith(PREFIX) and sp.end > a.lo and sp.start < a.hi:
            print(f"  {'  ' * sp.depth}{sp.name} {(sp.end - sp.start) * 1e-6:.3f} {sp.stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
