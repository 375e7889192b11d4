"""Count the solver's device launches from the benchmark's side.

``LaunchCounter`` wraps one ``Solver``'s ``solve_batch`` and ``feasible``
and, while it is recording, keeps each launch's bounds and result, with the
shapes of the graph it ran on. Iteration counts and statuses are read from
the results only when asked, after the work is done, so counting adds no
wait for the device. Each launch is also a ``bench.launch`` span in a trace.

A vmapped ``while_loop`` runs every lane until its slowest lane stops, so a
launch costs ``max(iters)`` batched iterations whatever its other lanes did.
"""
from __future__ import annotations

import jax
import numpy as np


def incidence_dims(problem) -> tuple[int, int, int]:
    """(n_vertices, n_edges, index_sets) of the incidence operator in ``problem``."""
    for op in (problem.P, problem.C):
        op = getattr(op, "inner", op)  # vertex cover's Transposed(Incidence)
        if op is not None and hasattr(op, "u") and hasattr(op, "n_vertices"):
            shape = np.shape(op.u)
            return int(op.n_vertices), int(shape[-1]), int(np.prod(shape[:-1], dtype=int))
    raise ValueError(f"problem {problem.name!r} has no incidence operator")


class LaunchCounter:
    def __init__(self, solver):
        self.recording = False
        self.recorded = 0  # launches recorded so far
        self.before_launch = None  # called before each recorded launch is dispatched
        self._pending: list[tuple[object, tuple, object]] = []
        batch, feasible = solver.solve_batch, solver.feasible

        def solve_batch(problem, bounds, **kw):
            return self._launch(batch, problem, bounds, kw)

        def single(problem, bound=None, **kw):
            return self._launch(feasible, problem, bound, kw)

        solver.solve_batch = solve_batch
        solver.feasible = single

    def _launch(self, fn, problem, bounds, kw):
        if self.recording and self.before_launch is not None:
            self.before_launch()
        with jax.profiler.TraceAnnotation("bench.launch"):
            res = fn(problem, bounds, **kw)
        if self.recording:
            self._pending.append((bounds, incidence_dims(problem), res))
            self.recorded += 1
        return res

    def take(self) -> list[dict]:
        """The launches recorded since the last call, with their counts."""
        from repro.core.mwu import Status

        out = []
        for bounds, (n_vertices, n_edges, index_sets), res in self._pending:
            iters = np.atleast_1d(np.asarray(res.iters))
            out.append({
                "lanes": int(iters.size),
                "n_vertices": n_vertices,
                "n_edges": n_edges,
                "index_sets": index_sets,
                "batched_iters": int(iters.max(initial=0)),
                "lane_iters": int(iters.sum()),
                "bounds": np.atleast_1d(np.asarray(bounds, np.float64)).tolist(),
                "feasible": (np.atleast_1d(np.asarray(res.status)) == Status.FEASIBLE).tolist(),
            })
        self._pending = []
        return out
