"""Reduce a JAX profiler trace to device busy time, op times and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps two
things: the operations each TPU ran (the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane) and the benchmark's own host spans (``bench.*``
``TraceAnnotation`` events). ``summarize`` then works on those plain records
alone, so a test can hand it a synthetic trace:

* the window is the ``bench.traced`` span, open while the profiler records;
* busy time is the union of a device's op intervals inside the window,
  averaged over the devices; an op that only holds others (``while``,
  ``conditional``, ``call``, whose body ops are events of their own) is
  left out, so the gaps between a loop's ops count as idle;
* an op's time is the sum of its events' durations inside the window; an op
  is named by a label built from the event's name and its string-valued
  stats (HLO op, module, category, framework op), which the op-name lists
  under ``opnames/`` match by regular expression;
* every idle gap inside the window is put down to the innermost ``bench.*``
  span open at its middle ("outside" where none is).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"\s(?:while|conditional|call)\(")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


class Interval(NamedTuple):
    label: str
    start_ns: float
    end_ns: float


@dataclass
class Trace:
    devices: dict[str, list[Interval]] = field(default_factory=dict)
    spans: list[Interval] = field(default_factory=list)


def _label(name: str, stats) -> str:
    parts = [name]
    for key, value in stats:
        if isinstance(value, str) and not key.startswith("_"):
            parts.append(f"{key}={value}")
    return " ".join(parts)


def load(trace_dir: Path) -> Trace:
    """The TPU ops and benchmark spans of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    out = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            labels: dict[str, str | None] = {}  # op name -> label, None for a container
            ops: list[Interval] = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    if name not in labels:
                        labels[name] = None if CONTAINER.search(name) else _label(name, ev.stats)
                    label = labels[name]
                    if label is not None:
                        start = ev.start_ns
                        ops.append(Interval(label, start, start + ev.duration_ns))
            out.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out.spans.append(Interval(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class SpanIndex:
    """The innermost open span at any time, for properly nested spans."""

    def __init__(self, spans: list[Interval]):
        # sweep the boundaries with a stack; each segment starts at a
        # boundary and carries the span on top of the stack
        bounds = []
        for s in spans:
            if s.label != WINDOW_SPAN:
                bounds.append((s.start_ns, 1, -s.end_ns, s.label))
                bounds.append((s.end_ns, 0, 0.0, s.label))
        bounds.sort()
        self.starts: list[float] = []
        self.labels: list[str] = []
        stack: list[str] = []
        for t, opening, _, label in bounds:
            if opening:
                stack.append(label)
            elif label in stack:
                stack.reverse()
                stack.remove(label)
                stack.reverse()
            self.starts.append(t)
            self.labels.append(stack[-1] if stack else "outside")

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        return self.labels[i] if i >= 0 else "outside"


@dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices
    n_devices: int
    op_s: dict[str, float]  # label -> seconds, mean over devices
    idle_by_span_s: dict[str, float]  # span -> idle seconds, mean over devices

    def op_seconds(self, patterns: list[re.Pattern]) -> float:
        return sum(s for label, s in self.op_s.items() if any(p.search(label) for p in patterns))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most time, summed by HLO name over programs
        (the graphs of a pool compile to programs that differ in shapes
        only), and the idle time by host span."""
        by_name: dict[str, float] = defaultdict(float)
        for label, s in self.op_s.items():
            by_name[label.split(" ")[0]] += s
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [list(kv) for kv in ops], "idle_gaps": [list(kv) for kv in gaps]}


def summarize(trace: Trace) -> Summary | None:
    """Busy, op and idle times inside the ``bench.traced`` span, or None."""
    windows = [s for s in trace.spans if s.label == WINDOW_SPAN]
    if not windows or not trace.devices:
        return None
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    spans = SpanIndex([s for s in trace.spans if s.end_ns > w0 and s.start_ns < w1])
    n = len(trace.devices)
    busy = 0.0
    op_s: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    for ops in trace.devices.values():
        clipped = []
        for iv in ops:
            a, b = max(iv.start_ns, w0), min(iv.end_ns, w1)
            if b > a:
                clipped.append((a, b))
                op_s[iv.label] += (b - a) / 1e9 / n
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged) / 1e9 / n
        cursor = w0
        for a, b in merged + [(w1, w1)]:
            if a > cursor:
                idle[spans.at((cursor + a) / 2)] += (a - cursor) / 1e9 / n
            cursor = max(cursor, b)
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy, n_devices=n, op_s=dict(op_s), idle_by_span_s=dict(idle)
    )


def read_patterns(path: Path, shapes: list[dict] | None = None) -> list[re.Pattern]:
    """One regular expression per line; blank lines and ``#`` comments skipped.

    A line may name the shapes of a launch: ``{E}`` its edges, ``{V}`` its
    vertices, ``{VL}`` and ``{EL}`` a vertex or edge vector of all its lanes
    as XLA prints it (``4,65536``; ``65536`` for one lane). Such a line
    becomes one pattern for each of ``shapes`` (dicts with ``n_vertices``,
    ``n_edges``, ``lanes``).
    """
    out = []
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        if "{" not in line:
            out.append(re.compile(line))
            continue
        for s in shapes or []:
            lanes = f"{s['lanes']}," if s["lanes"] > 1 else ""
            e_lanes = f",{s['lanes']}" if s["lanes"] > 1 else ""
            filled = (line.replace("{VL}", f"{lanes}{s['n_vertices']}").replace("{EL}", f"{s['n_edges']}{e_lanes}")
                      .replace("{E}", str(s["n_edges"])).replace("{V}", str(s["n_vertices"])))
            out.append(re.compile(filled))
    return out
