"""The solver's own instrumentation: host spans, launch counters, device scopes.

``Solver.solve`` writes ``solver.*`` spans to the profiler's trace and
their self times to ``Solution.timings``; it counts its launches' batched
iterations and its lanes at ``ITER_LIMIT`` where it reads the lanes back;
the MWU loop names its operators and line search with ``jax.named_scope``,
which ``repro.utils.hlo.op_scopes`` reads back from a compiled program.
The tests also hold the counts to the benchmark's own launch counter
(``perfbench/``) and check that the extra spans change nothing its trace
reduction reports but the span each idle gap falls in.
"""
from __future__ import annotations

import re
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import graph500, harness, trace
from perfbench.instrument import LaunchCounter
from repro.api import MWUOptions, Solver, Status
from repro.graphs import Graph, build

SCOPES = ("incidence.scatter", "incidence.gather", "mwu.linesearch")
SPAN_NAMES = {"solver.solve", "solver.round", "solver.dispatch", "solver.wait", "solver.readback", "solver.certify"}


def _lp(family: str, scale: int = 6, seed: int = 1):
    n, u, v = graph500.kron(scale, seed)
    return build(family, Graph(n=n, u=u, v=v, name=f"kron-{scale}-{seed}"))


# -- counters ------------------------------------------------------------------
@pytest.mark.parametrize("family", ["match", "vcover"])
def test_launch_counts_match_the_benchmark_counter(family):
    solver = Solver(MWUOptions(eps=0.1, max_iter=2000), batch_width=4)
    counter = LaunchCounter(solver)
    counter.recording = True
    sol = solver.solve(_lp(family))
    launches = counter.take()
    assert len(launches) > 1
    assert sol.batched_iters == sum(x["batched_iters"] for x in launches)
    assert sol.launched_lane_iters == sum(x["lanes"] * x["batched_iters"] for x in launches)
    assert sol.mwu_iters_total == sum(x["lane_iters"] for x in launches)
    assert sol.mwu_iters_total <= sol.launched_lane_iters


@pytest.mark.parametrize("family,lanes", [("match", 4), ("vcover", 1)])
def test_iter_limit_lanes_counts_lanes_stopped_at_max_iter(family, lanes):
    """Three iterations leave every lane of the first round short of a
    certificate: match's four-lane round and vertex cover's endpoint probe."""
    sol = Solver(MWUOptions(eps=0.1, max_iter=3), batch_width=4).solve(_lp(family))
    assert not sol.found and sol.status == Status.ITER_LIMIT
    assert sol.iter_limit_lanes == sol.feasibility_calls == lanes
    assert sol.batched_iters == 3 and sol.launched_lane_iters == 3 * lanes


def test_iter_limit_lanes_is_zero_when_every_lane_stops_on_its_own():
    sol = Solver(MWUOptions(eps=0.1, max_iter=2000), batch_width=4).solve(_lp("match"))
    assert sol.feasible and sol.iter_limit_lanes == 0


def test_engine_solutions_count_their_own_lanes_and_no_launches():
    """An engine launch serves many requests, so its solutions carry no
    launch counts; lanes at ITER_LIMIT are each request's own."""
    from repro.lpserve import LPEngine, LPServeConfig

    engine = LPEngine(LPServeConfig(opts=MWUOptions(eps=0.1, max_iter=3), lanes=2))
    sols = engine.solve_many([_lp("vcover"), _lp("vcover", seed=2)])
    for sol in sols:
        assert sol.iter_limit_lanes == sol.feasibility_calls == 1
        assert sol.batched_iters is None and sol.launched_lane_iters is None
        assert sol.timings is None


def test_dist_solver_inherits_the_spans_and_counts():
    from repro.dist import DistSolver

    opts = MWUOptions(eps=0.1, max_iter=2000)
    p = _lp("match")
    dist, plain = DistSolver(opts, batch_width=4).solve(p), Solver(opts, batch_width=4).solve(p)
    for key in ("batched_iters", "launched_lane_iters", "iter_limit_lanes", "mwu_iters_total"):
        assert getattr(dist, key) == getattr(plain, key), key
    assert set(dist.timings) == {"dispatch", "wait", "readback", "certify", "search"}


def test_dist_solver_waits_in_the_wait_span_and_still_counts_its_launches():
    """``DistSolver.solve_batch`` leaves its launch counts for later, so the
    host blocks on the device in ``solver.wait``, not in ``solver.dispatch``."""
    from repro.dist import DistSolver

    solver = DistSolver(MWUOptions(eps=0.1, max_iter=2000), batch_width=4)
    counter = LaunchCounter(solver)
    p = _lp("match")
    solver.solve(p)  # compile outside the timing
    counter.recording = True
    sol = solver.solve(p)
    launches = counter.take()
    assert sol.timings["wait"] > 0.5 * sum(sol.timings.values())
    assert sol.timings["wait"] > 3 * sol.timings["dispatch"]
    before = solver.dist_stats["mwu_iters"]  # both solves, read after the last launch
    assert solver.dist_stats["launches"] == 2 * len(launches)
    assert before == 2 * sol.mwu_iters_total
    assert solver.dist_stats["feasibility_calls"] == 2 * sol.feasibility_calls


# -- host spans ----------------------------------------------------------------
@pytest.mark.parametrize("family", ["match", "vcover"])
def test_timings_sum_to_the_solve_wall_time(family):
    solver = Solver(MWUOptions(eps=0.1, max_iter=2000), batch_width=4)
    p = _lp(family)
    solver.solve(p)  # compile outside the timing
    t0 = time.perf_counter()
    sol = solver.solve(p)
    wall = time.perf_counter() - t0
    assert set(sol.timings) == {"dispatch", "wait", "readback", "certify", "search"}
    assert all(v >= 0 for v in sol.timings.values())
    assert sol.timings["wait"] > 0 and sol.timings["certify"] > 0
    assert abs(sum(sol.timings.values()) - wall) <= max(0.05 * wall, 2e-3)


def test_solve_writes_its_spans_to_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    solver = Solver(MWUOptions(eps=0.1, max_iter=2000), batch_width=4)
    p = _lp("match")
    solver.solve(p)
    jax.profiler.start_trace(str(tmp_path))
    sol = solver.solve(p)
    jax.profiler.stop_trace()
    data = ProfileData.from_file(str(next(Path(tmp_path).rglob("*.xplane.pb"))))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events if ev.name.startswith("solver.")]
    assert {e[0] for e in events} == SPAN_NAMES
    assert all(e[3]["problem"] == p.name for e in events)
    rounds = sorted((e for e in events if e[0] == "solver.round"), key=lambda e: e[1])
    assert len(rounds) > 1 and [e[3]["round"] for e in rounds] == list(range(len(rounds)))
    (_, s0, s1, _), = [e for e in events if e[0] == "solver.solve"]
    assert all(s0 <= e[1] and e[2] <= s1 for e in events)
    for name in ("solver.dispatch", "solver.wait", "solver.readback"):
        inner = [e for e in events if e[0] == name]
        assert len(inner) == len(rounds)
        # each sits inside the round of the same index
        for e in inner:
            r = rounds[e[3]["round"]]
            assert r[1] <= e[1] and e[2] <= r[2]


# -- device scopes -------------------------------------------------------------
@pytest.mark.parametrize("family", ["match", "vcover"])
def test_named_scopes_reach_the_hlo_metadata(family):
    """Every stage's scope is in the batched program's op names, and the
    operator scopes follow the data: the scatter direction, a reduction over
    an ordered gather with no scatter in the loop, under ``incidence.scatter``
    and the gather direction under ``incidence.gather`` (vertex cover's
    transposed incidence too)."""
    p = _lp(family, scale=4)
    hlo = Solver(MWUOptions(eps=0.1)).lower_batch(p, np.array([1.0, 2.0])).as_text(dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    loop = [n for n in names if "/while/body/" in n]
    assert not any("scatter" in n.rsplit("/", 1)[-1] for n in loop)
    gathers = [n for n in loop if n.endswith("/gather")]
    assert all("/incidence.scatter/" in n or "/incidence.gather/" in n for n in gathers)
    for scope in ("incidence.scatter", "incidence.gather"):
        assert any(f"/{scope}/" in n for n in gathers), scope


@pytest.mark.parametrize("family", ["match", "vcover"])
def test_op_scopes_reads_the_scopes_back_from_the_compiled_program(family):
    """``op_scopes`` maps the compiled program's instructions, the names a
    profiler trace gives its device ops, to the scope each came from."""
    from repro.tracecheck.hlo_ir import parse_hlo
    from repro.utils.hlo import op_scopes

    p = _lp(family, scale=4)
    text = Solver(MWUOptions(eps=0.1)).lower_batch(p, np.array([1.0, 2.0])).compile().as_text()
    scopes = op_scopes(text, SCOPES)
    assert set(scopes.values()) == set(SCOPES)
    ops = {op.name: op for comp in parse_hlo(text).comps.values() for op in comp.ops}
    for name, scope in scopes.items():
        assert scope in re.search(r'op_name="([^"]*)"', ops[name].rest).group(1)
    assert all(scopes.get(op.name) == "incidence.scatter" for op in ops.values() if op.kind == "scatter")
    # the line search's own loop and the fusions it runs are under its scope
    body = {c for op in ops.values() if op.kind == "while" and scopes.get(op.name) == "mwu.linesearch"
            for c in op.called_comps()}
    assert body and any(scopes.get(n) == "mwu.linesearch" and ops[n].kind == "fusion"
                        for comp in parse_hlo(text).comps.values() if comp.name in body for n in comp.by_name)
    assert op_scopes(text, ()) == {}


# -- the benchmark's trace reduction with the solver's spans --------------------
V, E, LANES = 65536, 910200, 4
# op labels as a v5e trace gives them: the HLO text alone, layouts cut short
SCATTER = (f"%fusion.140 = f32[{LANES},{V}]{{0,1}} fusion(f32[{LANES},{V}]{{0,1}} %fusion.139, s32[{E}]{{0}} %gte, "
           f"f32[{E},{LANES}]{{1,0}} %bitcast.185), kind=kCustom, calls=%fused_computation.94")
GATHER = (f"%fusion.135 = f32[{E},{LANES}]{{1,0}} fusion(f32[{LANES},{V}]{{0,1}} %fusion.133, s32[{E}]{{0}} %pad), "
          "kind=kCustom, calls=%fused_computation.clone")
PROBE = "%fusion.21 = f32[4]{0} fusion(f32[4,65536]{0,1} %y, f32[4,65536]{0,1} %dy), kind=kLoop"


def _synthetic_trace(with_solver_spans: bool) -> trace.Trace:
    """Two launches of one solve. The device idles in the first launch
    (0-300 ns), between ops while the host waits (2600-2700, 7500-7600),
    while the host reads the first launch back (4200-5400) and after the
    solve (8100-10000)."""
    iv = trace.Interval
    spans = [iv(trace.WINDOW_SPAN, 0, 10_000), iv("bench.solve", 0, 9_000), iv("bench.launch", 100, 400),
             iv("bench.launch", 5_020, 5_250)]
    if with_solver_spans:
        spans += [iv("solver.solve", 50, 9_000),
                  iv("solver.round", 80, 5_000), iv("solver.dispatch", 90, 420), iv("solver.wait", 420, 4_300),
                  iv("solver.readback", 4_300, 4_950),
                  iv("solver.round", 5_000, 8_800), iv("solver.dispatch", 5_010, 5_300),
                  iv("solver.wait", 5_300, 8_200), iv("solver.readback", 8_200, 8_700),
                  iv("solver.certify", 8_800, 8_990)]
    ops = [iv(SCATTER, 300, 2_000), iv(GATHER, 2_000, 2_600), iv(PROBE, 2_700, 4_200),
           iv(SCATTER, 5_400, 7_000), iv(GATHER, 7_000, 7_500), iv(PROBE, 7_600, 8_100)]
    return trace.Trace(devices={"/device:TPU:0": ops}, spans=spans)


def _run_record(summary) -> harness.RunRecord:
    run = harness.RunRecord(harness.resolve("g500-match.s16"), "TPU v5 lite", 1)
    launch = {"lanes": LANES, "n_vertices": V, "n_edges": E, "index_sets": 1, "batched_iters": 2,
              "lane_iters": 6, "bounds": [1.0, 2.0, 3.0, 4.0], "feasible": [True, True, False, False]}
    run.launches = [launch, dict(launch)]
    run.solves = [{"graph": 0, "feasibility_calls": 8, "lane_iters": 12, "probes": 30, "launches": run.launches}]
    run.traced, run.trace, run.window_s, run.setup_s = 2, summary, 10.0, 20.0
    return run


def test_solver_spans_change_nothing_the_trace_reduction_reported():
    """The same trace with and without ``solver.*`` spans: idle gaps move
    from the solve onto the spans open in them, nothing else moves."""
    plain = trace.summarize(_synthetic_trace(False))
    spanned = trace.summarize(_synthetic_trace(True))
    assert (spanned.window_s, spanned.busy_s, spanned.n_devices, spanned.op_s, spanned.idle_share) == (
        plain.window_s, plain.busy_s, plain.n_devices, plain.op_s, plain.idle_share)
    assert sum(spanned.idle_by_span_s.values()) == pytest.approx(sum(plain.idle_by_span_s.values()))
    assert plain.idle_by_span_s == pytest.approx({"bench.launch": 300e-9, "bench.solve": 1400e-9, "outside": 1900e-9})
    assert spanned.idle_by_span_s == pytest.approx(
        {"bench.launch": 300e-9, "solver.wait": 200e-9, "solver.readback": 1200e-9, "outside": 1900e-9})
    cell = harness.resolve("g500-match.s16")
    assert {m["name"] for m in cell.per_layer} >= {"iter_ms", "scatter_roofline", "gather_roofline", "idle_share.solve"}
    before = harness.read_metrics(_run_record(plain), cell.per_layer)
    after = harness.read_metrics(_run_record(spanned), cell.per_layer)
    assert after == before and set(before) == {m["name"] for m in cell.per_layer}

