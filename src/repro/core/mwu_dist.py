"""DEPRECATED distributed MWU entry points — thin shims over ``repro.dist``.

The 2-D grid-partitioned driver that used to live here (hand-rolled
while_loop with grid-transpose collectives over a (data, model) mesh) is
superseded by the mesh-sharded solver layer:

* :class:`repro.dist.MeshPlan` + :class:`repro.dist.DistSolver` run the
  SAME core driver (``core.mwu._run``) under ``shard_map`` with 1-D
  edge-slab sharding and psum-completed constraint rows;
* the legacy 2-D layout itself survives as
  :func:`repro.sparsela.partition.partition_edges` (host-side
  preprocessing, still covered by ``tests/test_distributed.py``).

These shims keep the old call signatures and result types alive by
translating onto the new layer; importing this module emits one
``DeprecationWarning`` per process. New code should use ``repro.dist``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..sparsela.partition import Partition2D
from ..utils.deprecation import warn_once
from .mwu import MWUOptions, _run
from .operators import Incidence, OnesRow

__all__ = ["dist_matching_solve", "DistMWUResult", "make_pod_parallel_solver"]

warn_once(
    "core.mwu_dist",
    "repro.core.mwu_dist is deprecated; use repro.dist (MeshPlan + DistSolver) "
    "for mesh-sharded solves",
)


class DistMWUResult(NamedTuple):
    x: jax.Array  # (G, G, e_cell) edge shards (legacy cell layout)
    status: jax.Array
    iters: jax.Array
    probes: jax.Array
    objective: jax.Array  # <1, x>
    max_px: jax.Array


def _flatten_partition(part: Partition2D):
    """Cell layout -> global edge list + the cell indices to scatter back."""
    mask = np.asarray(part.mask)
    i_idx, j_idx, k_idx = np.nonzero(mask)
    u = np.asarray(part.u_loc)[i_idx, j_idx, k_idx] + i_idx * part.block
    v = np.asarray(part.v_loc)[i_idx, j_idx, k_idx] + j_idx * part.block
    return u.astype(np.int32), v.astype(np.int32), (i_idx, j_idx, k_idx)


def dist_matching_solve(part: Partition2D, n_vertices: int, bound: float,
                        mesh, eps: float = 0.1, max_iter: int = 5000):
    """Feasibility solve: exists x >= 0 with Mx <= 1, <1,x> >= bound.

    Deprecated shim: flattens the legacy 2-D cell partition back into a
    global edge list and runs :class:`repro.dist.DistSolver` with an
    edge-slab pod plan over all of ``mesh``'s devices. The result keeps
    the old (G, G, e_cell) x layout.
    """
    from ..api.problem import Problem
    from ..dist import DistSolver, MeshPlan

    u, v, cell_idx = _flatten_partition(part)
    prob = Problem(
        name="match",
        kind="packing",
        sense="max",
        bound_mode="objective_covering",
        P=Incidence(u=jnp.asarray(u), v=jnp.asarray(v), n_vertices=int(n_vertices)),
        c=jnp.ones((u.shape[0],), jnp.float32),
        lo=1.0,
        hi=float(bound),
        n_vars=int(u.shape[0]),
        nnz=2 * int(u.shape[0]),
    )
    n_devices = int(np.asarray(mesh.devices).size) if mesh is not None else 1
    solver = DistSolver(
        MWUOptions(eps=eps, step_rule="binary", max_iter=max_iter),
        plan=MeshPlan(pod=n_devices, data=1),
    )
    res = solver.feasible(prob, float(bound))
    x_flat = np.asarray(res.x)
    x_cells = np.zeros((part.grid, part.grid, part.e_cell), x_flat.dtype)
    x_cells[cell_idx] = x_flat
    return DistMWUResult(
        x=jnp.asarray(x_cells),
        status=res.status,
        iters=res.iters,
        probes=res.ls_probes,
        objective=jnp.asarray(x_flat.sum()),
        max_px=res.max_px,
    )


def make_pod_parallel_solver(mesh, G: int, block: int, n_vertices: int,
                             n_edges: int, eps: float = 0.1, max_iter: int = 5000,
                             ls_cap: int = 60):
    """Pod-parallel bound search (beyond-paper, DESIGN.md §5). Deprecated.

    Returns a jittable ``fn(bounds (n_pod,), u, v, mask) -> (status,
    iters, objective, max_px)``, each ``(n_pod,)``: every pod tests a
    different bound concurrently. The shim reassembles the legacy
    (G, G, e_cell) cell shards into a global edge list IN-graph (the
    inputs are replicated across the pod's data/model axes) and runs the
    unified core driver per pod — no cross-pod collectives, so pods
    finish independently. ``ls_cap`` is accepted for signature
    compatibility; the core step rules carry their own probe caps.

    New code: ``repro.dist.DistSolver.solve_batch`` with a ``data``-axis
    plan does the same fan-out over any problem family.
    """
    del ls_cap  # legacy knob of the hand-rolled line search
    n_pad = G * block
    opts = MWUOptions(eps=eps, step_rule="binary", max_iter=max_iter)
    p_mask = jnp.arange(n_pad) < n_vertices  # padded vertex rows stay out of smax

    def inner(bound_loc, u, v, msk):
        u_g = (u + jnp.arange(G, dtype=u.dtype)[:, None, None] * block).reshape(-1)
        v_g = (v + jnp.arange(G, dtype=v.dtype)[None, :, None] * block).reshape(-1)
        em = msk.reshape(-1)
        P_op = Incidence(u=u_g, v=v_g, n_vertices=n_pad, edge_mask=em)
        C_op = OnesRow(
            c=jnp.where(em, 1.0, 0.0).astype(jnp.float32),
            inv_bound=(1.0 / bound_loc[0]).astype(jnp.float32),
        )
        res = _run(P_op, C_op, opts, p_mask, None)
        obj = jnp.sum(jnp.where(em, res.x, 0.0))
        return res.status[None], res.iters[None], obj[None], res.max_px[None]

    def fn(bounds, u, v, msk):
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P("pod"), P(), P(), P()),
            out_specs=(P("pod"),) * 4,
            # per-pod results are replicated over the pod's own data/model
            # axes (inputs replicated, no collectives in the body) — not
            # expressible to the static rep checker.
            check_vma=False,
        )(bounds, u, v, msk)

    return fn
