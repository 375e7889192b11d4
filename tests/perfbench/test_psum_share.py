"""CPU test of ``psum_share``, the pod collectives' share of the device's busy
time in the four-chip cell. No test touches a TPU."""
from __future__ import annotations

import pytest
from test_perfbench import BENCH, SOLVE, hlo_module, synthetic_trace

from perfbench import harness, trace


def test_psum_share_is_the_pod_collectives_share_of_busy_time():
    """Four planes, each running the synthetic trace's ops and an all-reduce
    under ``pod.psum`` of its own length: the share is the scope's seconds
    over the busy seconds, each the mean over the four chips; a trace with
    no op under the scope, or no trace, reads None, not 0."""
    iv, t = trace.Interval, synthetic_trace()
    ops = t.devices["/device:TPU:0"]
    # plane i: busy 100..400, 400..400 + 50 (i + 1) under the scope, 600..700
    t.devices = {f"/device:TPU:{i}": ops + [iv("all-reduce.4", 400, 400 + 50 * (i + 1), SOLVE)] for i in range(4)}
    scopes = {"scatter.1": "incidence.scatter", "gather.2": "incidence.gather", "fusion.3": None}
    t.programs = {SOLVE: hlo_module(SOLVE, dict(scopes, **{"all-reduce.4": "pod.psum"}))}
    metric = [m for m in BENCH["per_layer"] if m["name"] == "psum_share"]
    run = harness.RunRecord(harness.resolve("g500-match.s16-pod4"), "TPU v5 lite", 4)
    run.trace = trace.summarize(t, {0, 1, 2, 3})
    assert run.trace.busy_s == pytest.approx((400 + 125) * 1e-9)
    assert harness.read_metrics(run, metric)["psum_share"]["value"] == pytest.approx(100.0 * 125 / 525)
    t.programs = {SOLVE: hlo_module(SOLVE, dict(scopes, **{"all-reduce.4": None}))}
    run.trace = trace.summarize(t, {0, 1, 2, 3})
    assert run.trace.scope_seconds("pod.psum") is None
    assert harness.read_metrics(run, metric) == {}
    run.trace = None
    assert harness.read_metrics(run, metric) == {}
