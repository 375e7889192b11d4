"""Drive one benchmark cell on the CPU, on as many devices as its ``chips`` asks.

The test session sees one device. A one-chip cell runs in the test's own
process; a cell on more chips runs in a child Python process that starts JAX
with that many host devices (``--xla_force_host_platform_device_count``).
Either way the run is ``run.run_cell(..., rehearse=True)``, with the same
planted fault, applied by the same function of this module, so a four-chip
cell is new files and entries only:

* a fault (``FAULTS``) patches the timed path underneath through ``patch``,
  a ``setattr``: the test's undoable one in-process, the plain one in a child;
* ``answer`` (``ANSWERS``) maps each x before it is checked: the control;
* ``watch_compiles`` (``compile_watch``) lists what compiles inside the
  driver's window, under the result's ``compiles``.

    python tests/perfbench/cell_runs.py '<request JSON>'

is the child: it prints the run's result as its last line of output.
"""
from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, run
from perfbench.calibrate import to_bfloat16

CHILD_TIMEOUT_S = 600
RETRIES = 2  # XLA's CPU collectives time out at random when cores are busy


# -- faults: each patches the timed path through ``patch`` -------------------
def stuck_step(patch):
    """Every MWU iteration returns its state unchanged."""
    from repro.core import mwu

    def stuck(P, C, eta, scale, step_fn, ls_eps, p_mask, c_mask, axis, carry):
        return carry._replace(it=carry.it + 1)

    patch(mwu, "_iteration", stuck)


def half_the_edges(patch):
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

    patch(Incidence, "matvec", half_scatter)
    patch(Incidence, "rmatvec", half_gather)


def altered_answer(patch):
    """Every certified x is halved where the solver produces it."""
    from repro.api import solver as api_solver

    certify = api_solver.certify_solution

    def altered(*args, **kw):
        sol = certify(*args, **kw)
        sol.x = np.asarray(sol.x) * 0.5
        return sol

    patch(api_solver, "certify_solution", altered)


def coarse_bracket(patch):
    """The bound search stops at a bracket of 1+eps, not the configuration's 1+eps/2."""
    from repro.api import Solver

    init = Solver.__init__

    def coarse(self, opts=None, **kw):
        init(self, opts, **dict(kw, rel_tol=opts.eps))

    patch(Solver, "__init__", coarse)


FAULTS = {f.__name__: f for f in (stuck_step, half_the_edges, altered_answer, coarse_bracket)}
ANSWERS = {f.__name__: f for f in (to_bfloat16,)}


def compile_watch(cell, patch) -> list[str]:
    """The list that fills with each program compiled inside the driver's window."""
    module = harness.driver(cell)
    patch(harness, "driver", lambda _: module)  # the run takes the watched class
    compiles, in_window = [], [False]

    class Log(logging.Handler):
        def emit(self, record):
            if in_window[0] and record.getMessage().startswith("Compiling"):
                compiles.append(record.getMessage()[:120])

    window = module.Driver.window

    def watched(self, seconds):
        in_window[0] = True
        try:
            return window(self, seconds)
        finally:
            in_window[0] = False

    patch(module.Driver, "window", watched)
    log = logging.getLogger("jax._src.interpreters.pxla")
    patch(log, "handlers", [*log.handlers, Log(level=logging.DEBUG)])
    patch(log, "level", logging.DEBUG)
    return compiles


def _run(cell, seed, fault, answer, watch, patch) -> dict:
    if fault is not None:
        fault(patch)
    compiles = compile_watch(cell, patch) if watch else None
    result = run.run_cell(cell, seed, 0.0, traced=False, rehearse=True, answer=answer)
    if compiles is not None:
        result["compiles"] = compiles
    return result


# -- the one way the tests drive a cell -----------------------------------------
def drive(cell, *, seed=2**31 + 3, fault=None, answer=None, watch_compiles=False) -> dict:
    """One rehearsed run of ``cell`` on ``cell.chips`` CPU devices: its result object.

    ``fault`` is one of ``FAULTS``, ``answer`` one of ``ANSWERS``. A child
    resolves the cell by name in the ``BENCHMARK.json`` beside
    ``cell.bench_dir``, then takes this cell's configuration and traffic,
    which a test may have cut down.
    """
    if cell.chips == 1:
        with pytest.MonkeyPatch.context() as mp:
            return _run(cell, seed, fault, answer, watch_compiles, mp.setattr)
    request = {
        "root": str(Path(cell.bench_dir).parent), "bench_dir": str(cell.bench_dir), "cell": cell.name,
        "config": cell.config, "traffic": cell.traffic, "seed": seed, "watch_compiles": watch_compiles,
        "fault": fault and fault.__name__, "answer": answer and answer.__name__,
    }
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{os.environ.get('XLA_FLAGS', '')} --xla_force_host_platform_device_count={cell.chips}",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT), str(harness.ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    for attempt in range(RETRIES + 1):
        done = subprocess.run([sys.executable, __file__, json.dumps(request)], capture_output=True, text=True,
                              env=env, timeout=CHILD_TIMEOUT_S)
        if done.returncode == 0:
            return json.loads(done.stdout.strip().splitlines()[-1])
        if "rendezvous" not in done.stderr.lower() or attempt == RETRIES:
            raise AssertionError(f"cell {cell.name} on {cell.chips} devices exited {done.returncode}:\n"
                                 f"{done.stderr[-4000:]}")


def _child(request: dict) -> None:
    import jax

    jax.config.update("jax_enable_x64", True)  # as the test session has it
    cell = harness.resolve(request["cell"], root=Path(request["root"]), bench_dir=Path(request["bench_dir"]))
    cell.config, cell.traffic = request["config"], request["traffic"]
    fault = request["fault"] and FAULTS[request["fault"]]
    answer = request["answer"] and ANSWERS[request["answer"]]
    result = _run(cell, request["seed"], fault, answer, request["watch_compiles"], setattr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    _child(json.loads(sys.argv[1]))
