"""The solve loop on an edge-slab ``DistSolver`` over all of the cell's chips.

``solve_loop.Driver`` with the solver built as
``DistSolver(plan=MeshPlan(pod=<chips>))``: each chip holds a contiguous
slab of every graph's edges, and the vertex vectors are replicated, each
constraint image completed by a ``psum``. Set-up places each graph on the
mesh once (``DistSolver.place``: the slabs padded and transferred), so no
solve in the window pads or transfers an edge array. Traffic parameters as
``solve_loop``'s.
"""
from __future__ import annotations

from pathlib import Path

from perfbench import harness, lp

solve_loop = harness.load_module(Path(__file__).with_name("solve_loop.py"), "perfbench_driver_solve_loop")


class Driver(solve_loop.Driver):
    def make_solver(self):
        from repro.dist import DistSolver, MeshPlan

        return DistSolver(lp.options(self.config), plan=MeshPlan(pod=len(self.devices)),
                          batch_width=self.traffic["batch_width"])

    def place(self, problem):
        return self.solver.place(problem)
