"""CPU tests of the chip benchmark's harness (``perfbench/``). No test touches a TPU."""
from __future__ import annotations

import json
import re
import shutil

import numpy as np
import pytest
from cell_runs import drive

from perfbench import graph500, harness, lp, opbytes, peaks, reference, trace
from perfbench.instrument import LaunchCounter, incidence_dims

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# -- BENCHMARK.json and the files each name resolves to ---------------------
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(cell)
    assert (harness.HERE / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert c.config["lp"] in reference.CHECKS
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for m in c.per_layer:
        assert m["moves"] in names, (cell, m["name"])


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert (ROOT / BENCH["command"][1]).is_file()
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200 and len(c["why"]) <= 200
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg) and cfg["reduced"] == c["reduced"]
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 2)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert set(m.get("workloads", [])) <= cells and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A dummy cell, with a configuration and a traffic mix of its own, runs
    from files added beside the benchmark's, none of which is edited."""
    from perfbench import run

    bench_dir = tmp_path / "perfbench"
    shutil.copytree(harness.HERE, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((bench_dir / "configs" / "g500-match.json").read_text())
    (bench_dir / "configs" / "g500-match-tiny.json").write_text(json.dumps(dict(cfg, scale=6)))
    (bench_dir / "traffic" / "dummy.json").write_text(
        json.dumps({"driver": "solve_loop", "batch_width": 4, "graph_seeds": [3]}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="g500-match-tiny", file="perfbench/configs/g500-match-tiny.json"))
    bench["workloads"].append({"name": "g500-match-tiny.dummy", "config": "g500-match-tiny", "traffic": "dummy",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "g500-match.s16" in m.get("workloads", []):
            m["workloads"].append("g500-match-tiny.dummy")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.resolve("g500-match-tiny.dummy", root=tmp_path, bench_dir=bench_dir)
    assert cell.config["scale"] == 6 and cell.traffic["graph_seeds"] == [3]
    result = run.run_cell(cell, 2**31 + 11, 0.0, traced=False, rehearse=True)
    assert result["correct"] and result["attempted"] == 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"solve_s", "setup_s"}
    assert list(result)[-1] == "checks" and set(result["checks"]) == {"violation", "gap", "bracket"}
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_nothing_compiles_inside_the_window(cell):
    """Set-up warms every program a solve runs, the lane splits among them."""
    c = harness.resolve(cell)
    c.config = dict(c.config, scale={"match": 8, "vcover": 7}[c.config["lp"]], max_iter=4322)
    c.traffic = dict(c.traffic, graph_seeds=[1, 2])
    result = drive(c, seed=2**31 + 13, watch_compiles=True)
    assert result["correct"] and result["attempted"] == 2
    assert result["compiles"] == []


def test_unknown_workload_is_an_error():
    with pytest.raises(harness.BenchmarkError):
        harness.resolve("no-such.cell")


def test_a_run_without_a_tpu_exits_nonzero(capsys):
    from perfbench import run

    assert run.main(["--workload", "g500-vcover.s13", "--seed", "1", "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err


# -- the trace reduction -----------------------------------------------------
def hlo_module(program: str, ops: dict[str, str | None]) -> str:
    """A compiled program's HLO text as the profile's HLO proto prints it:
    one instruction per op label (an instruction's HLO text, as a trace
    names it), under the named scope given (None: under none of the solver's)."""
    lines = [f"HloModule {program.split('(')[0]}, entry_computation_layout={{()->f32[]}}", "",
             "ENTRY %main.1 () -> f32[] {"]
    for label, scope in ops.items():
        path = f"jit(_feasibility_batch)/vmap(while)/body/vmap({scope})/op" if scope else "jit(_feasibility_batch)/add"
        name = label.split(" ")[0]
        text = label if " = " in label else f"%{name} = f32[4]{{0}} {name.split('.')[0]}(f32[4]{{0}} %p)"
        lines.append(f'  {text}, metadata={{op_name="{path}" source_file="x.py"}}')
    return "\n".join(lines + ["}"])


SOLVE = "jit_solve(1)"


def synthetic_trace() -> trace.Trace:
    iv = trace.Interval
    return trace.Trace(
        devices={
            "/device:TPU:0": [
                iv("scatter.1 hlo_module=jit_solve", 100, 300, SOLVE),
                iv("gather.2 hlo_module=jit_solve", 250, 400, SOLVE),
                iv("fusion.3 hlo_module=jit_solve", 600, 700, SOLVE),
                iv("fusion.3 hlo_module=jit_solve", 1100, 1200, SOLVE),  # after the window
            ],
            "/device:TPU:1": [iv("all-reduce.4", 0, 1000, SOLVE)],
        },
        spans=[
            iv(trace.WINDOW_SPAN, 0, 1000),
            iv("bench.solve", 0, 800),
            iv("bench.launch", 40, 450),
        ],
        programs={SOLVE: hlo_module(SOLVE, {"scatter.1": "incidence.scatter", "gather.2": "incidence.gather",
                                            "fusion.3": None, "all-reduce.4": None})},
    )


def test_trace_reduction_gives_known_times():
    s = trace.summarize(synthetic_trace())
    assert s.window_s == pytest.approx(1e-6)
    assert s.n_devices == 2
    # device 0 busy 100..400 and 600..700 = 400 ns, device 1 1000 ns
    assert s.busy_s == pytest.approx((400 + 1000) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(1 - 700 / 1000)
    # op time is summed per device then averaged over the two devices
    assert s.scope_seconds("incidence.scatter") == pytest.approx(200e-9 / 2)
    assert s.scope_seconds("incidence.gather") == pytest.approx(150e-9 / 2)
    assert s.op_s[SOLVE, "all-reduce.4"] == pytest.approx(1000e-9 / 2)
    assert s.unmapped == []
    # device 0's gaps: 0..100 in the launch, 400..600 in the solve, 700..1000 outside
    assert s.idle_by_span_s == pytest.approx(
        {"bench.launch": 100e-9 / 2, "bench.solve": 200e-9 / 2, "outside": 300e-9 / 2})
    b = s.breakdown()
    assert b["device_ops"][0] == ["all-reduce.4", pytest.approx(500e-9)]
    assert b["idle_gaps"][0] == ["outside", pytest.approx(150e-9)]


def test_only_the_planes_of_the_runs_chips_are_read():
    """A one-chip cell on a four-chip host: the profiler records all four
    planes, the run's chip (JAX id 2) ran every op, and the reading is that
    plane's alone, not diluted by three idle chips."""
    t = synthetic_trace()
    ops = t.devices["/device:TPU:0"]
    t.devices = {f"/device:TPU:{i}": ops if i == 2 else [] for i in range(4)}
    s = trace.summarize(t, {2})
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(400e-9)
    assert s.idle_share == pytest.approx(1 - 400 / 1000)
    assert s.scope_seconds("incidence.scatter") == pytest.approx(200e-9)
    assert s.idle_by_span_s == pytest.approx({"bench.launch": 100e-9, "bench.solve": 200e-9, "outside": 300e-9})
    every = trace.summarize(t)
    assert every.n_devices == 4 and every.busy_s == pytest.approx(s.busy_s / 4)
    assert trace.summarize(t, {7}) is None  # no plane of the run's chips: nothing is read


def test_two_programs_that_reuse_instruction_names_keep_their_own_scopes():
    """Two graphs of a pool compile to programs that differ in shapes alone and
    reuse instruction names; each op's time lands on its own program's scope."""
    iv, a, b = trace.Interval, "jit__feasibility_batch(11)", "jit__feasibility_batch(22)"
    t = trace.Trace(
        devices={"/device:TPU:0": [iv("%fusion.118 = f32[4,64]", 100, 300, a),
                                   iv("%fusion.118 = f32[4,64]", 400, 450, b),
                                   iv("%fusion.7 = f32[4,90]", 500, 530, a), iv("%fusion.7 = f32[4,80]", 600, 700, b)]},
        spans=[iv(trace.WINDOW_SPAN, 0, 1000)],
        programs={a: hlo_module(a, {"fusion.118": "incidence.scatter", "fusion.7": "incidence.gather"}),
                  b: hlo_module(b, {"fusion.118": "incidence.gather", "fusion.7": "incidence.scatter"})},
    )
    s = trace.summarize(t)
    assert s.scope_seconds("incidence.scatter") == pytest.approx((200 + 100) * 1e-9)
    assert s.scope_seconds("incidence.gather") == pytest.approx((50 + 30) * 1e-9)
    # the breakdown still names ops as the trace does, summed over programs
    assert s.breakdown()["device_ops"][0] == ["%fusion.118", pytest.approx(250e-9)]


def _roofline_run(summary, lp_kind="match", n_devices=1) -> harness.RunRecord:
    cell = harness.resolve({"match": "g500-match.s16", "vcover": "g500-vcover.s13"}[lp_kind])
    run = harness.RunRecord(cell, "TPU v5 lite", n_devices)
    run.launches = [{"lanes": 4, "n_vertices": 64, "n_edges": 500, "index_sets": 1, "batched_iters": 10,
                     "lane_iters": 40, "bounds": [1.0] * 4, "feasible": [True] * 4}]
    run.solves = [{"launches": run.launches}]
    run.traced, run.trace = 1, summary
    return run


@pytest.mark.parametrize("op", ["scatter", "gather"])
def test_a_roofline_is_of_the_chips_the_run_used(op):
    """The same launches and per-chip scope seconds on four chips read a
    quarter of the share they read on one: the whole graph's bytes against
    four chips' bandwidth."""
    iv = trace.Interval
    t = trace.Trace(devices={"/device:TPU:0": [iv("%fusion.1 = f32[4,64]", 100, 300, SOLVE)]},
                    spans=[iv(trace.WINDOW_SPAN, 0, 1000)],
                    programs={SOLVE: hlo_module(SOLVE, {"fusion.1": f"incidence.{op}"})})
    s = trace.summarize(t)
    metric = [m for m in BENCH["per_layer"] if m["name"] == f"{op}_roofline"]
    one, four = (harness.read_metrics(_roofline_run(s, n_devices=n), metric)[f"{op}_roofline"]["value"]
                 for n in (1, 4))
    per_call = opbytes.incidence_bytes(64, 500, 4)  # _roofline_run's launch: 10 batched iterations
    start = opbytes.incidence_bytes(64, 500, 1) if op == "scatter" else 0  # match's y = Px0
    hbm = peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert one == pytest.approx(100.0 * (10 * per_call + start) / 200e-9 / hbm)
    assert four == pytest.approx(one / 4)


def test_no_op_under_a_scope_reads_none_not_zero():
    iv = trace.Interval
    t = trace.Trace(devices={"/device:TPU:0": [iv("%fusion.1 = f32[4,64]", 100, 300, SOLVE)]},
                    spans=[iv(trace.WINDOW_SPAN, 0, 1000)],
                    programs={SOLVE: hlo_module(SOLVE, {"fusion.1": "incidence.scatter"})})
    s = trace.summarize(t)
    assert s.scope_seconds("incidence.gather") is None
    metrics = [m for m in BENCH["per_layer"] if m["name"].endswith("_roofline")]
    got = harness.read_metrics(_roofline_run(s), metrics)
    assert set(got) == {"scatter_roofline"} and got["scatter_roofline"]["value"] > 0


def test_a_program_without_its_hlo_is_named_and_reads_no_scope():
    """Ops of a program whose HLO the trace lacks: no roofline is read from
    another program's scopes, and the program is named."""
    iv, other = trace.Interval, "jit__feasibility_batch(99)"
    t = trace.Trace(devices={"/device:TPU:0": [iv("%fusion.1 = f32[4,64]", 100, 300, SOLVE),
                                               iv("%fusion.1 = f32[4,64]", 400, 500, other),
                                               iv("%copy.2 = f32[4]", 600, 610)]},
                    spans=[iv(trace.WINDOW_SPAN, 0, 1000)],
                    programs={SOLVE: hlo_module(SOLVE, {"fusion.1": "incidence.scatter"})})
    s = trace.summarize(t)
    assert s.unmapped == [other] and s.unplaced_s == pytest.approx(10e-9)
    assert s.scope_seconds("incidence.scatter") is None
    metrics = [m for m in BENCH["per_layer"] if m["name"].endswith("_roofline")]
    assert harness.read_metrics(_roofline_run(s), metrics) == {}


class _Event:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns, self.stats = name, start, end - start, []


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Event(*e) for e in events]


def test_device_ops_take_the_program_whose_run_holds_them():
    plane = type("Plane", (), {"lines": [
        _Line(trace.OPS_LINE, [("%fusion.1 = f32[4]", 110, 150), ("%while.2 = (f32[4]) while((f32[4]) %t)", 150, 390),
                               ("%fusion.1 = f32[4]", 520, 560), ("%copy.3 = f32[4]", 700, 710)]),
        _Line(trace.MODULES_LINE, [("jit_f(2)", 500, 600), ("jit_f(1)", 100, 400)]),
    ]})()
    ops = trace._device_ops(plane)
    assert [(iv.label, iv.program) for iv in ops] == [
        ("%fusion.1 = f32[4]", "jit_f(1)"), ("%fusion.1 = f32[4]", "jit_f(2)"), ("%copy.3 = f32[4]", trace.NO_PROGRAM)]
    assert trace.CONTAINER.search("%while.244 = (f32[4,910200]) while((f32[4,910200]) %tuple.173), condition=%c")


def test_trace_without_a_window_reads_nothing():
    t = synthetic_trace()
    t.spans = [s for s in t.spans if s.label != trace.WINDOW_SPAN]
    assert trace.summarize(t) is None


def test_trace_load_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(256)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.solve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(tmp_path)
    labels = [s.label for s in t.spans]
    assert trace.WINDOW_SPAN in labels and "bench.solve" in labels
    assert t.devices == {}  # a CPU run has no TPU plane, so nothing is busy
    assert trace.summarize(t) is None


def test_trace_load_reads_each_programs_hlo_from_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("incidence.scatter"):
            y = jnp.zeros(8).at[jnp.arange(256) % 8].add(x)
        return y * 2

    x = jnp.ones(256)
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.enable_hlo_proto = True
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    programs = trace.load(tmp_path).programs
    names = [p for p in programs if p.startswith("jit_f(")]
    assert len(names) == 1 and names[0].endswith(")")
    assert set(trace.op_scopes(programs[names[0]], ("incidence.scatter",)).values()) == {"incidence.scatter"}


def test_op_name_lists_parse():
    shapes = [{"n_vertices": 4096, "n_edges": 48556, "lanes": 4}]
    for f in (harness.HERE / "opnames").glob("*.txt"):
        assert trace.read_patterns(f, shapes), f.name


# op names as a v5e trace of Graph500-16 match (4 lanes) gives them, layouts cut short
V5E_MATCH_OPS = {
    "%fusion.140 = f32[4,65536]{0,1:T(8,128)S(1)} fusion(f32[4,65536]{0,1:T(8,128)S(1)} %fusion.139, "
    "s32[910200]{0:T(1024)} %get-tuple-element.987, f32[910200,4]{1,0:T(8,128)} %bitcast.185, "
    "s32[910336]{0:T(1024)} %pad_clamp_fusion.11), kind=kCustom, calls=%fused_computation.94.clone.clone": "scatter",
    "%fusion.4 = f32[65536]{0:T(1024)} fusion(s32[910200]{0:T(1024)} %compare_select_fusion.13, "
    "f32[910200]{0:T(1024)} %broadcast.94, f32[]{:T(128)} %constant.198), kind=kCustom, "
    "calls=%fused_computation.192": "scatter",
    "%sort.4 = (s32[910200]{0:T(1024)}, s32[910200]{0:T(1024)S(1)}) sort(s32[910200]{0:T(1024)S(1)} "
    "%fusion.134, s32[910200]{0:T(1024)S(1)} %iota.4), dimensions={0}, to_apply=%compare": "scatter",
    "%fusion.135 = f32[910200,4]{1,0:T(8,128)} fusion(f32[4,65536]{0,1:T(8,128)S(1)} %fusion.133, "
    "s32[910336]{0:T(1024)S(1)} %pad_clamp_fusion.8), kind=kCustom, calls=%fused_computation.clone.clone": "gather",
    "%fusion.138 = (f32[4]{0:T(128)S(1)}, f32[4]{0:T(128)S(1)}, f32[4,910200]{0,1:T(8,128)}) "
    "fusion(f32[910200]{0:T(1024)S(1)} %get-tuple-element.1186, f32[910200,4]{1,0:T(8,128)} %fusion.135), "
    "kind=kLoop, calls=%fused_computation.198.clone.clone": None,
    "%add_select_fusion.11 = f32[4,65536]{0,1:T(8,128)} fusion(f32[4,65536]{0,1:T(8,128)} %get-tuple-element.1163, "
    "f32[4,65536]{0,1:T(8,128)S(1)} %fusion.140, f32[4]{0:T(128)S(1)} %get-tuple-element.1013), "
    "kind=kLoop, calls=%fused_computation.107.clone.clone": None,
}


@pytest.mark.parametrize("op", ["scatter", "gather"])
def test_op_name_lists_pick_the_operators_out_of_a_v5e_trace(op):
    run = harness.RunRecord(harness.resolve("g500-match.s16"), "TPU v5 lite", 1)
    run.launches = [{"n_vertices": 65536, "n_edges": 910200, "lanes": 4}]
    patterns = harness.opnames(run, op)
    for label, which in V5E_MATCH_OPS.items():
        assert any(p.search(label) for p in patterns) == (which == op), label[:40]
    assert trace.CONTAINER.search("%while.244 = (f32[4,910200]) while((f32[4,910200]) %tuple.173), condition=%c")


@pytest.mark.parametrize("op", ["scatter", "gather"])
def test_ops_in_no_program_are_read_by_their_op_names(op):
    """Ops that ran outside every program's run have no HLO to look a scope
    up in: they count by ``opnames/``, beside the ops read by scope."""
    program = "jit__feasibility_batch(7)"
    ms = {label: (i + 1) * 1e-3 for i, label in enumerate(V5E_MATCH_OPS)}
    op_s = {(trace.NO_PROGRAM, label): t for label, t in ms.items()}
    op_s[program, "%fusion.9 = f32[4,65536]"] = 0.5
    s = trace.Summary(window_s=1.0, busy_s=sum(op_s.values()), n_devices=1, op_s=op_s, idle_by_span_s={},
                      programs={program: hlo_module(program, {"fusion.9": f"incidence.{op}"})})
    run = _roofline_run(s)
    run.launches[:] = [dict(run.launches[0], n_vertices=65536, n_edges=910200)]
    assert s.unmapped == [] and s.unplaced_s == pytest.approx(sum(ms.values()))
    want = 0.5 + sum(t for label, t in ms.items() if V5E_MATCH_OPS[label] == op)
    assert s.scope_seconds(f"incidence.{op}", harness.opnames(run, op)) == pytest.approx(want)
    assert s.scope_seconds(f"incidence.{op}") == pytest.approx(0.5)
    metric = [m for m in BENCH["per_layer"] if m["name"] == f"{op}_roofline"]
    assert harness.read_metrics(run, metric)[f"{op}_roofline"]["value"] > 0


@pytest.mark.parametrize("op", ["scatter", "gather"])
def test_v5e_match_ops_land_on_their_scopes(op):
    """The op labels of a v5e trace, looked up in the scope map of a program
    that holds them, land on the operator each belongs to."""
    program = "jit__feasibility_batch(4427881338085217842)"
    text = hlo_module(program, {label: which and f"incidence.{which}" for label, which in V5E_MATCH_OPS.items()})
    ms = {label: (i + 1) * 1e-3 for i, label in enumerate(V5E_MATCH_OPS)}
    s = trace.Summary(window_s=1.0, busy_s=sum(ms.values()), n_devices=1,
                      op_s={(program, label): t for label, t in ms.items()}, idle_by_span_s={},
                      programs={program: text})
    want = sum(t for label, t in ms.items() if V5E_MATCH_OPS[label] == op)
    assert s.scope_seconds(f"incidence.{op}") == pytest.approx(want)


def _gather_reduce_matvec(self, x):
    """The scatter direction with no scatter: each endpoint's edges summed
    from a prefix sum in endpoint order, read back at the segment ends."""
    import jax.numpy as jnp

    xw = x * self._w(x.dtype)
    ends = jnp.concatenate([self.u, self.v])
    order = jnp.argsort(ends)
    sums = jnp.concatenate([jnp.zeros(1, x.dtype), jnp.cumsum(jnp.concatenate([xw, xw])[order])])
    cuts = jnp.searchsorted(ends[order], jnp.arange(self.n_vertices + 1))
    return sums[cuts[1:]] - sums[cuts[:-1]]


def _compiled_batch(family: str):
    """The batched program of a tiny Graph500 LP as the CPU compiles it, and its launch's shapes."""
    import jax

    from repro.api import Solver
    from repro.core.operators import Incidence

    cfg = json.loads((ROOT / f"perfbench/configs/g500-{family}.json").read_text())
    n, u, v = graph500.kron(6, 1)
    p = lp.problem(cfg, n, u, v, "kron-6-1")
    op = next(getattr(o, "inner", o) for o in (p.P, p.C) if isinstance(getattr(o, "inner", o), Incidence))
    x = np.linspace(0.0, 1.0, len(u))
    assert np.allclose(op.matvec(x), np.bincount(u, x, n) + np.bincount(v, x, n))
    text = Solver(lp.options(cfg), batch_width=4).lower_batch(p, np.linspace(1.0, 2.0, 4)).compile().as_text()
    jax.clear_caches()  # the next variant traces afresh
    n_vertices, n_edges, index_sets = incidence_dims(p)
    return text, {"lanes": 4, "n_vertices": n_vertices, "n_edges": n_edges, "index_sets": index_sets,
                  "batched_iters": 10, "lane_iters": 40, "bounds": [1.0] * 4, "feasible": [True] * 4}


@pytest.mark.parametrize("family", ["match", "vcover"])
def test_a_matvec_that_does_not_scatter_keeps_the_scatter_roofline(family, monkeypatch):
    """Compiled with XLA's scatter-add and with a gather-reduce of the same
    operator under the same scope, the batched program puts instructions
    under ``incidence.scatter`` both times, a scatter only the first time;
    the bytes are the same, and the roofline reads a positive share of each."""
    import jax

    from perfbench.metrics import scatter_roofline
    from repro.core.operators import Incidence
    from repro.tracecheck.hlo_ir import parse_hlo
    from repro.utils import hlo

    jax.clear_caches()
    variants = [_compiled_batch(family)]
    monkeypatch.setattr(Incidence, "matvec", jax.named_scope("incidence.scatter")(_gather_reduce_matvec))
    variants.append(_compiled_batch(family))
    metric = [m for m in BENCH["per_layer"] if m["name"] == "scatter_roofline"]
    program = "jit__feasibility_batch(1)"
    moved, reads = [], []
    for (text, launch), scatters in zip(variants, (True, False)):
        ops = {op.name: op.kind for comp in parse_hlo(text).comps.values() for op in comp.ops}
        scoped = trace.op_scopes(text, ("incidence.scatter",))
        assert scoped and any(ops[name] == "scatter" for name in scoped) == scatters
        scopes = ("incidence.scatter", "incidence.gather", "mwu.linesearch")  # the copy reads as the program's own
        assert trace.op_scopes(text, scopes) == hlo.op_scopes(text, scopes)
        s = trace.Summary(window_s=1.0, busy_s=len(ops) * 1e-6, n_devices=1, idle_by_span_s={},
                          op_s={(program, f"%{name} = f32[] {kind}()"): 1e-6 for name, kind in ops.items()},
                          programs={program: text})
        assert s.scope_seconds("incidence.scatter") == pytest.approx(len(scoped) * 1e-6)
        run = _roofline_run(s, family)
        run.launches[:] = [launch]
        moved.append(scatter_roofline.moved(run, launch))
        reads.append(harness.read_metrics(run, metric)["scatter_roofline"]["value"])
    assert moved[0] == moved[1] and all(r > 0 for r in reads)


# -- the yardstick: bytes, peaks, generator, reference -----------------------
def test_minimum_bytes_match_a_hand_count():
    # triangle: 3 edges, 3 vertices, 2 lanes sharing one index set:
    # u and v (3 int32 each) = 24 bytes; per lane an edge vector (12 bytes)
    # and a vertex vector (12 bytes) = 48 bytes
    assert opbytes.incidence_bytes(3, 3, lanes=2) == 24 + 48
    # stacked instances read one index set per lane
    assert opbytes.incidence_bytes(3, 3, lanes=2, index_sets=2) == 48 + 48
    # Graph500-16 match, 4 lanes: 7.3 MB of indices, 14.6 MB of edge vector, 1.0 MB of vertex vector
    assert opbytes.incidence_bytes(65536, 910200, 4) == 2 * 910200 * 4 + 4 * (910200 + 65536) * 4


def test_peak_table_is_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9 and "Google Cloud" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


@pytest.mark.parametrize("scale,seed", [(6, 1), (8, 2), (10, 3)])
def test_copied_generator_matches_the_program(scale, seed):
    from repro.graphs import kron

    n, u, v = graph500.kron(scale, seed)
    g = kron(scale, seed=seed, edgefactor=16)
    assert n == g.n and np.array_equal(u, g.u) and np.array_equal(v, g.v)


def _lp_by_highs(n, u, v, family):
    import scipy.sparse as sp
    from scipy.optimize import linprog

    m = len(u)
    inc = sp.csr_matrix((np.ones(2 * m), (np.r_[u, v], np.r_[np.arange(m), np.arange(m)])), shape=(n, m))
    if family == "match":
        r = linprog(-np.ones(m), A_ub=inc, b_ub=np.ones(n), bounds=(0, None), method="highs")
        return -r.fun
    r = linprog(np.ones(n), A_ub=-inc.T, b_ub=-np.ones(m), bounds=(0, None), method="highs")
    return r.fun


@pytest.mark.parametrize("family", ["match", "vcover"])
@pytest.mark.parametrize("graph", ["kron7", "triangle", "path", "star"])
def test_reference_optimum_equals_the_lp(graph, family):
    if graph == "kron7":
        n, u, v = graph500.kron(7, 4)
    else:
        edges = {"triangle": [(0, 1), (1, 2), (0, 2)], "path": [(0, 1), (1, 2), (2, 3), (3, 4)],
                 "star": [(0, i) for i in range(1, 6)]}[graph]
        n = 1 + max(max(e) for e in edges)
        u, v = graph500.canonical_edges(n, np.array(edges))
    assert reference.lp_optimum(n, u, v) == pytest.approx(_lp_by_highs(n, u, v, family), abs=1e-6)


def test_certificate_check_rejects_a_perturbed_x():
    n, u, v = graph500.kron(7, 2)
    opt = reference.lp_optimum(n, u, v)
    deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    x = 1.0 / np.maximum(deg[u], deg[v])  # feasible: every load <= 1
    ok = reference.match_check(n, u, v, x, opt)
    assert ok["violation"] == 0.0 and ok["gap"] > 0
    bad = x.copy()
    bad[np.argmax(deg[u])] += 0.5
    assert reference.match_check(n, u, v, bad, opt)["violation"] >= 0.4
    bad = x.copy()
    bad[0] = -0.1
    assert reference.match_check(n, u, v, bad, opt)["violation"] == pytest.approx(0.1)
    # vertex cover: all halves is feasible with objective n/2
    y = np.full(n, 0.5)
    assert reference.vcover_check(n, u, v, y, opt)["violation"] == 0.0
    y[u[0]] = 0.25
    assert reference.vcover_check(n, u, v, y, opt)["violation"] == pytest.approx(0.25)
    assert reference.vcover_check(n, u, v, np.full(n, 0.6), opt)["gap"] == pytest.approx(0.6 * n / opt - 1)


# -- the counters --------------------------------------------------------------
def test_launch_counter_counts_batched_and_lane_iterations():
    from repro.api import MWUOptions, Solver
    from repro.graphs import Graph, build

    n, u, v = graph500.kron(7, 1)
    solver = Solver(MWUOptions(eps=0.1, max_iter=4321), batch_width=4)
    counter = LaunchCounter(solver)
    counter.recording = True
    p = build("vcover", Graph(n=n, u=u, v=v))
    sol = solver.solve(p)
    launches = counter.take()
    assert sum(x["lanes"] for x in launches) == sol.feasibility_calls
    assert sum(x["lane_iters"] for x in launches) == sol.mwu_iters_total
    assert launches[0]["lanes"] == 1  # vertex cover's endpoint probe
    assert all(x["lanes"] * x["batched_iters"] >= x["lane_iters"] for x in launches)
    assert all((x["n_vertices"], x["n_edges"], x["index_sets"]) == (n, len(u), 1) for x in launches)
    assert all(len(x["bounds"]) == len(x["feasible"]) == x["lanes"] for x in launches)
    assert launches[0]["bounds"] == [pytest.approx(float(p.hi))]
    assert launches[0]["feasible"] == [True]
    assert 0 < lp.bracket(launches, sol.bound, float(p.lo), float(p.hi), p.feasible_side) <= 0.05
    assert counter.take() == []


def _launch(bounds, feasible):
    return {"bounds": bounds, "feasible": feasible}


@pytest.mark.parametrize("side,launches,bound,want", [
    # a maximum: the certified 1.2 against the least failure above it, 1.26
    ("lo", [_launch([1.0, 1.2, 1.4, 1.6], [True, True, False, False]),
            _launch([1.22, 1.26, 1.3, 1.35], [False, False, False, False])], 1.2, 1.22 / 1.2),
    # a maximum where every probe was feasible: against the problem's hi
    ("lo", [_launch([1.0, 1.5], [True, True])], 1.5, 2.0 / 1.5),
    # a minimum: the certified 1.5 against the largest failure below it; a
    # failure above it (a lane at max_iter) does not count
    ("hi", [_launch([2.0], [True]), _launch([1.2, 1.4, 1.5, 1.8], [False, False, True, False])], 1.5, 1.5 / 1.4),
    # a minimum with no failure: against the problem's lo
    ("hi", [_launch([2.0], [True])], 2.0, 2.0 / 1.0),
])
def test_bracket_is_read_from_the_probes(side, launches, bound, want):
    assert lp.bracket(launches, bound, 1.0, 2.0, side) == pytest.approx(want - 1.0)
