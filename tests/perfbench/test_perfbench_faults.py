"""The comparison that decides ``correct``, shown to fail: the control and planted faults.

Every test drives the rest of a benchmark run on the CPU (``rehearse``
skips the look for a chip) at a size a test run can hold, with the timed
path broken underneath, and sees ``correct`` come out false; each also
runs the same cell sound and sees it true. Each broken run uses an
``MWUOptions`` of its own (a distinct ``max_iter``), so the solver traces
afresh with the fault in place and no other test meets its compiled code.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from perfbench import harness, run
from perfbench.calibrate import to_bfloat16


def tiny(cell: str, max_iter: int, graph_seeds=(2, 5)) -> harness.Cell:
    c = harness.resolve(cell)
    scale = {"match": 7, "vcover": 6}[c.config["lp"]]
    c.config = dict(c.config, scale=scale, max_iter=max_iter)
    c.traffic = dict(c.traffic, graph_seeds=list(graph_seeds))
    return c


CELLS = [w["name"] for w in json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def drive(cell, answer=None) -> dict:
    return run.run_cell(cell, 2**31 + 3, 0.0, traced=False, rehearse=True, answer=answer)


@pytest.mark.parametrize("name", CELLS)
def test_the_bfloat16_control_fails_and_the_program_passes(name):
    cell = tiny(name, 5000)
    sound = drive(cell)
    assert sound["correct"], sound["checks"]
    control = drive(cell, answer=to_bfloat16)
    assert not control["correct"]
    assert control["checks"]["violation"]["value"] > control["checks"]["violation"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_a_step_that_returns_its_state_unchanged_fails(name, monkeypatch):
    from repro.core import mwu

    def stuck(P, C, eta, scale, step_fn, ls_eps, p_mask, c_mask, axis, carry):
        return carry._replace(it=carry.it + 1)

    monkeypatch.setattr(mwu, "_iteration", stuck)
    result = drive(tiny(name, 301))
    assert not result["correct"] and result["failed"] == result["attempted"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_half_the_edges_left_out_fails(name, monkeypatch):
    """Both incidence products see half of the edge list: the scatter doubles
    what the first half adds up, the gather hands the rest the first half's
    values."""
    import jax.numpy as jnp
    from repro.core.operators import Incidence

    def half_scatter(self, x):
        k = self.u.shape[-1] // 2
        xw = 2.0 * (x * self._w(x.dtype))[..., :k]
        out = jnp.zeros((self.n_vertices,), dtype=x.dtype)
        return out.at[self.u[..., :k]].add(xw).at[self.v[..., :k]].add(xw)

    def half_gather(self, y):
        m = self.u.shape[-1]
        k = m // 2
        g = y[self.u[:k]] + y[self.v[:k]]
        return jnp.tile(g, -(-m // k))[:m] * self._w(y.dtype)

    monkeypatch.setattr(Incidence, "matvec", half_scatter)
    monkeypatch.setattr(Incidence, "rmatvec", half_gather)
    result = drive(tiny(name, 302))
    assert not result["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_altered_where_it_is_produced_fails(name, monkeypatch):
    from repro.api import solver as api_solver

    certify = api_solver.certify_solution

    def altered(*args, **kw):
        sol = certify(*args, **kw)
        sol.x = np.asarray(sol.x) * 0.5
        return sol

    monkeypatch.setattr(api_solver, "certify_solution", altered)
    result = drive(tiny(name, 303))
    assert not result["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_a_bound_search_stopped_at_a_coarser_bracket_fails(name, monkeypatch):
    """The search stops at a bracket of 1+eps, not the configuration's 1+eps/2.

    On some graphs the two stop after the same round (match's seeds 2 and 5
    at this size) and the fault changes nothing; seeds 1 and 4 it changes.
    """
    from repro.api import Solver

    init = Solver.__init__

    def coarse(self, opts=None, **kw):
        init(self, opts, **dict(kw, rel_tol=opts.eps))

    monkeypatch.setattr(Solver, "__init__", coarse)
    result = drive(tiny(name, 305, graph_seeds=(1, 4)))
    assert not result["correct"]
    assert result["checks"]["bracket"]["value"] > result["checks"]["bracket"]["limit"]
