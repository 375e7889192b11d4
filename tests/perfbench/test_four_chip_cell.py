"""A four-chip cell is new files and entries only: driven on four CPU devices.

A dummy cell in a copy of the benchmark, with a configuration at test scale,
a traffic mix and a ``chips: 4`` workload of its own, runs the solve loop on
an edge-slab ``DistSolver`` (``fixtures/pod_solve_loop.py``, copied into the
copy's ``drivers/``) through ``cell_runs.drive``, which runs it in a child
process with four host devices. Its own file, so that it gets a test worker
of its own.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest
from cell_runs import drive, stuck_step

from perfbench import harness
from perfbench.calibrate import to_bfloat16

CELL = "g500-match-pod4.dummy"


@pytest.fixture(scope="module")
def cell(tmp_path_factory) -> harness.Cell:
    root = tmp_path_factory.mktemp("four_chips")
    bench_dir = root / "perfbench"
    shutil.copytree(harness.HERE, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(Path(__file__).with_name("fixtures") / "pod_solve_loop.py", bench_dir / "drivers")
    cfg = json.loads((bench_dir / "configs" / "g500-match.json").read_text())
    (bench_dir / "configs" / "g500-match-pod4.json").write_text(json.dumps(dict(cfg, scale=7)))
    (bench_dir / "traffic" / "dummy-pod4.json").write_text(
        json.dumps({"driver": "pod_solve_loop", "batch_width": 4, "graph_seeds": [2, 5]}))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="g500-match-pod4",
                                 file="perfbench/configs/g500-match-pod4.json"))
    bench["workloads"].append({"name": CELL, "config": "g500-match-pod4", "traffic": "dummy-pod4", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "g500-match.s16" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.resolve(CELL, root=root, bench_dir=bench_dir)


def test_a_four_chip_cell_runs_on_four_devices(cell):
    assert cell.chips == 4 and cell.config["scale"] == 7
    result = drive(cell, seed=2**31 + 17, watch_compiles=True)
    assert result["correct"] and result["attempted"] == 2 and result["failed"] == 0, result["checks"]
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 4
    assert result["compiles"] == []
    assert set(result["metrics"]) == {"solve_s", "setup_s"}


def test_the_bfloat16_control_fails_on_four_devices(cell):
    control = drive(cell, answer=to_bfloat16)
    assert not control["correct"]
    assert control["checks"]["violation"]["value"] > control["checks"]["violation"]["limit"]


def test_a_stuck_step_fails_on_four_devices(cell):
    result = drive(cell, fault=stuck_step)
    assert not result["correct"] and result["failed"] == result["attempted"] > 0
