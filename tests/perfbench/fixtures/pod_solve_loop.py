"""The solve loop on an edge-slab ``DistSolver`` over all of the cell's chips.

A test fixture: a test copies it into a copy of the benchmark's
``drivers/``, beside ``solve_loop.py``, to drive a four-chip cell. It is
``solve_loop.Driver`` with the solver built as
``DistSolver(plan=MeshPlan(pod=<chips>))``; each problem stays where it was
built, and the solver puts its edge slabs on the mesh.
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
        return problem
