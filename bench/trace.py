"""The profiler trace of a run's traced slice, reduced to what the metric
readers and the result line's ``breakdown`` need:

- device busy time: the union of the intervals in which an operation ran
  on a device, inside the slice, averaged over the devices that ran any;
- the slice's length, from the first to the last of the benchmark's own
  host spans (``bench.pickup``, ``bench.ingest``, ``bench.poll``);
- device time per XLA program (the ``XLA Modules`` line) and per
  operation or kernel (the ``XLA Ops`` line);
- the idle gaps, each labelled with the benchmark host span open at its
  midpoint.

Reading the ``.xplane.pb`` (``load``) is kept apart from the reduction
(``reduce``), so the reduction runs on events stored as JSON as well.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List, Tuple

HOST_SPANS = ("bench.pickup", "bench.ingest", "bench.poll")
DEVICE_PREFIX = "/device:"
MODULES, OPS = "XLA Modules", "XLA Ops"

Event = Tuple[str, float, float]  # (name, start ns, duration ns)


class UnknownDevice(KeyError):
    """A device kind that ``peaks.json`` does not hold."""


def peaks_for(kind: str, path: pathlib.Path) -> Dict[str, float]:
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {path} (has {sorted(table)})")
    return table[kind]


@dataclasses.dataclass
class Events:
    """Device lines by plane, and the benchmark's host spans."""

    device: Dict[str, Dict[str, List[Event]]]
    host: List[Event]

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Events":
        raw = json.loads(text)
        dev = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()} for p, lines in raw["device"].items()}
        return cls(dev, [tuple(e) for e in raw["host"]])


def load(path: pathlib.Path) -> Events:
    """Events of one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
            if lines:
                device[plane.name] = lines
        else:
            for line in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name in HOST_SPANS
                )
    return Events(device, host)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    modules: Dict[str, float]            # device seconds per XLA program
    ops: Dict[str, float]                # device seconds per operation
    idle_by_span: Dict[str, float]       # idle device seconds by host span
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, match) -> float:
        """Device seconds of the programs whose name ``match`` accepts."""
        return sum(s for name, s in self.modules.items() if match(name))

    def breakdown(self) -> Dict[str, List[list]]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top], "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(ev: Events) -> TraceSummary:
    if not ev.device:
        raise ValueError("the trace holds no device plane: nothing ran on a device")
    if ev.host:
        lo = min(s for _, s, _ in ev.host)
        hi = max(s + d for _, s, d in ev.host)
    else:
        spans = [(s, s + d) for lines in ev.device.values() for evs in lines.values() for _, s, d in evs]
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    spans_sorted = sorted((s, s + d, n) for n, s, d in ev.host)
    busy_total = 0.0
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    idle_by_span: Dict[str, float] = {}
    used = 0
    for lines in ev.device.values():
        base = lines.get(OPS) or lines.get(MODULES) or []
        busy = _union(_clip([(s, s + d) for _, s, d in base], lo, hi))
        if not busy:
            continue
        used += 1
        busy_total += sum(e - s for s, e in busy)
        for key, table in ((MODULES, modules), (OPS, ops)):
            for name, s, d in lines.get(key, []):
                for cs, ce in _clip([(s, s + d)], lo, hi):
                    table[name] = table.get(name, 0.0) + (ce - cs) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = 0.5 * (gs + ge)
            label = "host:none"
            for s, e, n in spans_sorted:
                if s > mid:
                    break
                if e >= mid:
                    label = f"host:{n}"
            idle_by_span[label] = idle_by_span.get(label, 0.0) + (ge - gs) * 1e-9
    if used == 0:
        raise ValueError("no operation ran on a device inside the traced slice")
    scale = 1.0 / used
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 * scale,
        modules={k: v * scale for k, v in modules.items()},
        ops={k: v * scale for k, v in ops.items()},
        idle_by_span={k: v * scale for k, v in idle_by_span.items()},
        devices=used,
    )


def reduce_dir(trace_dir: pathlib.Path, kind: str, peaks_path: pathlib.Path) -> TraceSummary:
    """The slice a run traced into ``trace_dir``; fails on a device kind
    the peaks table does not hold."""
    peaks_for(kind, peaks_path)
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(load(files[-1]))
