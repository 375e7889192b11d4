"""Reduce a JAX profiler trace to device busy time, op times by named scope, and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, recorded
with ``enable_hlo_proto``, and keeps three things: the operations each TPU
ran (the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane), each with the
program it ran in (the ``XLA Modules`` event around it, named
``jit_<function>(<program id>)``); the compiled HLO of each of those
programs (the HLO protos the profile keeps under the same names on its
``/host:metadata`` plane); and the benchmark's own host spans (``bench.*``
``TraceAnnotation`` events). ``summarize`` then works on those plain records
alone, so a test can hand it a synthetic trace:

* the window is the ``bench.traced`` span, open while the profiler records;
* the devices are the chips the run used: the profiler records a plane for
  every TPU of the host, and ``/device:TPU:<n>`` is the chip whose JAX
  device id is ``n``, so the other planes are left out;
* busy time is the union of a device's op intervals inside the window,
  averaged over the devices; an op that only holds others (``while``,
  ``conditional``, ``call``, whose body ops are events of their own) is
  left out, so the gaps between a loop's ops count as idle;
* an op's time is the sum of its events' durations inside the window, kept
  by program and label (the HLO text of the instruction, as the trace
  names it);
* ``Summary.scope_seconds`` sums the ops that their own program's compiled
  HLO puts under a ``jax.named_scope`` (``op_scopes``): the
  programs of one pool differ in shapes alone and reuse instruction names,
  so an op is looked up in its own program only. A fusion takes the scope
  of its root. An op outside every program's run (a trace with no ``XLA
  Modules`` line) has no HLO to look up, and is matched by its own HLO text
  against an op-name list (``read_patterns``) instead;
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

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
NO_PROGRAM = "<no program>"  # an op that ran outside every XLA Modules event
CONTAINER = re.compile(r"\s(?:while|conditional|call)\(")
INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=[^\n]*?op_name="([^"]*)"', re.M)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"


class Interval(NamedTuple):
    label: str
    start_ns: float
    end_ns: float
    program: str = NO_PROGRAM  # a device op's program; spans have none


@dataclass
class Trace:
    devices: dict[str, list[Interval]] = field(default_factory=dict)
    spans: list[Interval] = field(default_factory=list)
    programs: dict[str, str] = field(default_factory=dict)  # program -> its compiled HLO text


def _label(name: str, stats) -> str:
    parts = [name]
    for key, value in stats:
        if isinstance(value, str) and not key.startswith("_"):
            parts.append(f"{key}={value}")
    return " ".join(parts)


def load(trace_dir: Path) -> Trace:
    """The TPU ops, their programs' HLO and the benchmark spans of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    out = Trace(programs=hlo_texts(files[-1]))
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            out.devices[plane.name] = _device_ops(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out.spans.append(Interval(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def _device_ops(plane) -> list[Interval]:
    """The ops of one device plane, each with the program whose run holds its start."""
    labels: dict[str, str | None] = {}  # op name -> label, None for a container
    events, modules = [], []
    for line in plane.lines:
        if line.name == OPS_LINE:
            events = list(line.events)
        elif line.name == MODULES_LINE:
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events)
    starts = [m[0] for m in modules]
    ops: list[Interval] = []
    for ev in events:
        name = ev.name
        if name not in labels:
            labels[name] = None if CONTAINER.search(name) else _label(name, ev.stats)
        label = labels[name]
        if label is None:
            continue
        start = ev.start_ns
        i = bisect.bisect_right(starts, start) - 1
        program = modules[i][2] if i >= 0 and start < modules[i][1] else NO_PROGRAM
        ops.append(Interval(label, start, start + ev.duration_ns, program))
    return ops


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf message:
    an int for a varint, a memoryview for the rest."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            value = varint()
        elif kind == 2:
            size = varint()
            value, i = buf[i:i + size], i + size
        else:
            size = {1: 8, 5: 4}[kind]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def hlo_texts(path: Path) -> dict[str, str]:
    """Program name -> compiled HLO text (with ``op_name`` metadata), from
    the HLO protos of the profile's ``/host:metadata`` plane.

    ``ProfileData`` does not reach a plane's event metadata, where the protos
    are, so this reads the few fields it needs from the file: ``XSpace.planes``
    (1); ``XPlane.name`` (2) and ``event_metadata`` (4, a map whose entries
    hold the key in 1 and an ``XEventMetadata`` in 2); ``XEventMetadata.name``
    (2) and ``stats`` (5); ``XStat.bytes_value`` (6), an ``xla.HloProto`` whose
    field 1 is the ``HloModuleProto``.
    """
    from jaxlib import _jax as hlo  # JAX's own HLO bindings: no public API reads an HLO proto

    options = hlo.HloPrintOptions()
    options.print_metadata = True
    out = {}
    for number, plane in _fields(memoryview(Path(path).read_bytes())):
        if number != 1:
            continue
        name, entries = None, []
        for f, value in _fields(plane):
            if f == 2:
                name = bytes(value).decode()
                if name != METADATA_PLANE:
                    break
            elif f == 4:
                entries.append(value)
        if name != METADATA_PLANE:
            continue
        for entry in entries:
            meta = dict(_fields(entry)).get(2, b"")
            program, protos = None, []
            for f, value in _fields(meta):
                if f == 2:
                    program = bytes(value).decode()
                elif f == 5:
                    protos += [v for g, v in _fields(value) if g == 6]
            for proto in protos:
                module = dict(_fields(proto)).get(1)
                if program and module is not None:
                    out[program] = hlo.HloModule.from_serialized_hlo_module_proto(bytes(module)).to_string(options)
    return out


def op_scopes(text: str, scopes) -> dict[str, str]:
    """Instruction name -> the innermost of ``scopes`` in its ``op_name``.

    ``text`` is a compiled program's HLO and ``scopes`` names of
    ``jax.named_scope``; a transform can wrap a scope in the name
    (``vmap(incidence.scatter)``). Instructions under none of ``scopes`` are
    left out. The benchmark's own copy of ``repro.utils.hlo.op_scopes``, so
    that no change to the program changes how its time is read.
    """
    out = {}
    for name, op_name in INSTRUCTION.findall(text):
        inner = [part for part in re.split(r"[/()]", op_name) if part in scopes]
        if inner:
            out[name] = inner[-1]
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
    op_s: dict[tuple[str, str], float]  # (program, label) -> seconds, mean over devices
    idle_by_span_s: dict[str, float]  # span -> idle seconds, mean over devices
    programs: dict[str, str] = field(default_factory=dict)  # program -> its compiled HLO text

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def unmapped(self) -> list[str]:
        """The programs that ran ops in the window and whose HLO the trace lacks."""
        return sorted({p for p, _ in self.op_s if p != NO_PROGRAM and p not in self.programs})

    @property
    def unplaced_s(self) -> float:
        """Device seconds of the ops that ran outside every program's run."""
        return sum(s for (program, _), s in self.op_s.items() if program == NO_PROGRAM)

    def scope_seconds(self, scope: str, unplaced: list[re.Pattern] = ()) -> float | None:
        """Device seconds of the ops under ``scope``.

        An op is looked up in its own program's HLO; one that ran in no
        program counts where its label matches one of ``unplaced``, the
        operation's op-name list. None where no op counts, or where some
        program that ran in the window has no HLO to look its ops up in
        (``unmapped``).
        """
        if self.unmapped:
            return None
        scoped = {p: op_scopes(self.programs[p], (scope,)) for p, _ in self.op_s if p != NO_PROGRAM}
        times = [s for (program, label), s in self.op_s.items()
                 if (any(p.search(label) for p in unplaced) if program == NO_PROGRAM
                     else label.split(" ")[0].lstrip("%") in scoped[program])]
        return sum(times) if times else None

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most time, summed by HLO name over programs
        (the graphs of a pool compile to programs that differ in shapes
        only), and the idle time by host span."""
        by_name: dict[str, float] = defaultdict(float)
        for (_, label), s in self.op_s.items():
            by_name[label.split(" ")[0]] += s
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [list(kv) for kv in ops], "idle_gaps": [list(kv) for kv in gaps]}


def summarize(trace: Trace, device_ids=None) -> Summary | None:
    """Busy, op and idle times inside the ``bench.traced`` span, or None.

    ``device_ids``: the JAX ids of the chips the run used; only their planes
    are read (all planes where None).
    """
    windows = [s for s in trace.spans if s.label == WINDOW_SPAN]
    planes = [ops for name, ops in trace.devices.items()
              if device_ids is None or int(DEVICE_PLANE.match(name).group(1)) in device_ids]
    if not windows or not planes:
        return None
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    spans = SpanIndex([s for s in trace.spans if s.end_ns > w0 and s.start_ns < w1])
    n = len(planes)
    busy = 0.0
    op_s: dict[tuple[str, str], float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    for ops in planes:
        clipped = []
        for iv in ops:
            a, b = max(iv.start_ns, w0), min(iv.end_ns, w1)
            if b > a:
                clipped.append((a, b))
                op_s[iv.program, iv.label] += (b - a) / 1e9 / n
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged) / 1e9 / n
        cursor = w0
        for a, b in merged + [(w1, w1)]:
            if a > cursor:
                idle[spans.at((cursor + a) / 2)] += (a - cursor) / 1e9 / n
            cursor = max(cursor, b)
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy, n_devices=n, op_s=dict(op_s), idle_by_span_s=dict(idle),
        programs=trace.programs,
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
