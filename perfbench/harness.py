"""Find a cell's files by name and turn one run's record into its result line.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by the name that ``BENCHMARK.json`` gives it:

* ``configs``' ``file``: the configuration (its sizes, precision, limits);
* ``traffic/<traffic>.json``: the mix, which names the driver that runs it;
* ``drivers/<driver>.py``: one general driver per kind of mix (a ``Driver``
  with ``setup``, ``window(seconds)``, ``record``, ``free``, ``check``
  and a ``counter``, its ``instrument.LaunchCounter``);
* ``metrics/<metric>.py``: one reader per metric, ``read(run) -> float | None``;
  a reader of device time finds an operation by the ``jax.named_scope`` the
  program gives it (``trace.Summary.scope_seconds``), not by XLA's op names;
* ``opnames/<op>.txt``: the HLO text of one operation's ops, read only for
  ops that a trace puts in no program, and so has no scopes for.

A new cell, metric or configuration is new files and new entries; no file
here changes.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent  # the benchmark's own directory
ROOT = HERE.parent  # the checkout


class BenchmarkError(Exception):
    """The run cannot start: a file or a name is missing, or the chip is."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: Path = HERE


@dataclass
class RunRecord:
    """What a driver measured in one run; the metric readers read this."""

    cell: Cell
    device_kind: str
    n_devices: int
    setup_s: float = math.nan
    window_s: float = math.nan
    solves: list[dict] = field(default_factory=list)  # solve loop: one per solve
    launches: list[dict] = field(default_factory=list)  # every launch in the window
    trace: object = None  # trace.Summary of a --trace 1 run
    traced: int = 0  # how many of ``launches`` ran while the profiler was on

    @property
    def traced_launches(self) -> list[dict]:
        return self.launches[: self.traced]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchmarkError(f"missing {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchmarkError(f"missing {path}")
    return json.loads(path.read_text())


def resolve(workload: str, root: Path = ROOT, bench_dir: Path = HERE) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with its files."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchmarkError(f"workload {workload!r} names no known config {w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a list is reported wherever its end-to-end one is
    per_layer = [m for m in bench["per_layer"] if workload in m.get("workloads", [workload] if m["moves"] in e2e_names else [])]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer, bench_dir)


def driver(cell: Cell):
    name = cell.traffic["driver"]
    return load_module(cell.bench_dir / "drivers" / f"{name}.py", f"perfbench_driver_{name}")


def read_metrics(run: RunRecord, metrics: list[dict]) -> dict:
    """{name: {"value", "unit"}} of each metric whose reader finds something."""
    out = {}
    for m in metrics:
        reader = load_module(run.cell.bench_dir / "metrics" / f"{m['name']}.py", f"perfbench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def opnames(run: RunRecord, op: str):
    """The trace patterns of ``opnames/<op>.txt`` for every launch shape of the run.

    Each shape also appears with one lane: a loop's start can run an
    operator once for all lanes (match's y = Px0).
    """
    from . import trace

    shapes = {(x["n_vertices"], x["n_edges"], lanes) for x in run.launches for lanes in (1, x["lanes"])}
    shapes = [{"n_vertices": v, "n_edges": e, "lanes": n} for v, e, n in sorted(shapes)]
    return trace.read_patterns(run.cell.bench_dir / "opnames" / f"{op}.txt", shapes)


def verdict(checks: dict, attempted: int, failed: int) -> bool:
    """True when something was attempted, nothing failed, every number is within its limit."""
    within = all(c["value"] <= c["limit"] for c in checks.values())
    return attempted > 0 and failed == 0 and within


def check_lines(checks: dict) -> list[str]:
    return [f"check {k} {c['value']!r} limit {c['limit']!r}" for k, c in checks.items()]
