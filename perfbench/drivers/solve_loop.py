"""Back-to-back solves of a pool of graphs: time to a certified solution.

Traffic parameters:

* ``graph_seeds`` -- the Graph500 generator seeds of the pool, at the
  configuration's ``scale``;
* ``batch_width`` -- bounds per search round (``Solver(batch_width=...)``).

Set-up draws the pool, builds each LP, puts it on the device, and warms
every program the window runs with a whole search over trivially feasible
bounds on each LP's operators (``lp.easy_problem``).
The window solves the pool in an order drawn from the run's seed, pass
after pass, and ends with the first pass to end after ``seconds``: every
run does the same whole passes, so the work does not depend on the order.
Each solve ends when its certified x is on the host. After the window,
every solve's x is checked in float64 against the exact optimum of its
graph, and its bound search's final bracket is read from its launches.

A driver for another solver subclasses ``Driver`` and gives its own
``make_solver`` and ``place``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from perfbench import lp
from perfbench.instrument import LaunchCounter


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.devices = devices
        self.graphs: list[tuple] = []
        self.problems: list = []
        self.ends: list[tuple[float, float, str]] = []  # each problem's (lo, hi, feasible side)
        self.solves: list[dict] = []
        self.answers: list[tuple] = []  # (graph, certified x or None, certified bound, launches)

    def make_solver(self):
        """The solver the window drives: one chip's ``Solver``."""
        from repro.api import Solver

        return Solver(lp.options(self.config), batch_width=self.traffic["batch_width"])

    def place(self, problem):
        """``problem`` where the solver reads it: on the cell's one chip."""
        return jax.device_put(problem, self.devices[0])

    def setup(self) -> None:
        cfg, tr = self.config, self.traffic
        self.solver = self.make_solver()
        for s in tr["graph_seeds"]:
            n, u, v = lp.graph(cfg, cfg["scale"], s)
            self.graphs.append((n, u, v))
            p = lp.problem(cfg, n, u, v, f"kron-{cfg['scale']}-{s}")
            self.ends.append((float(p.lo), float(p.hi), p.feasible_side))
            self.problems.append(self.place(p))
        self.counter = LaunchCounter(self.solver)
        for p in self.problems:
            jax.block_until_ready(self.solver.solve(lp.easy_problem(p, self.devices[0])).last_result)
        self.order = np.random.default_rng(abs(self.seed)).permutation(len(self.problems))

    def window(self, seconds: float) -> float:
        self.counter.recording = True
        t0 = time.perf_counter()
        while True:
            for i in self.order:
                with jax.profiler.TraceAnnotation("bench.solve"):
                    sol = self.solver.solve(self.problems[i])
                    jax.block_until_ready(sol.last_result)
                launches = self.counter.take()
                self.solves.append({
                    "graph": int(i),
                    "feasibility_calls": sol.feasibility_calls,
                    "lane_iters": sol.mwu_iters_total,
                    "probes": sol.ls_probes_total,
                    "launches": launches,
                })
                self.answers.append((int(i), sol.x if sol.feasible else None, sol.bound, launches))
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        self.counter.recording = False
        return t1 - t0

    def record(self, run) -> None:
        run.solves = self.solves
        run.launches = [launch for s in self.solves for launch in s["launches"]]

    def free(self) -> None:
        self.problems.clear()
        self.solver = self.counter = None

    def check(self, answer=lambda x: x) -> tuple[dict, int, int]:
        """Every solve's x against the exact optimum, and its bracket: (checks, attempted, failed)."""
        answers = [(i, x, {"bracket": lp.bracket(launches, bound, *self.ends[i])})
                   for i, x, bound, launches in self.answers]
        return lp.check_answers(self.config, self.graphs, answers, answer)
