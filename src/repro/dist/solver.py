"""``DistSolver``: the core MWU while_loop under ``shard_map``.

The single-device :class:`repro.api.Solver` already vmaps the jitted
``lax.while_loop`` across bounds and stacked instances. This module
wraps that exact driver — same ``core.mwu._run``, same kernel dispatch,
same options — in a ``shard_map`` over a :class:`~repro.dist.mesh.MeshPlan`:

* lanes (bounds x instances) slab across ``data`` — zero communication,
  the paper's rank-level bound sweep;
* each lane's variable space slabs across ``pod`` (``repro.dist.shard``),
  with the constraint-space coupling psum-completed per matvec — the
  paper's edge-partitioned within-solve scheme.

Two execution shapes, chosen host-side:

* **vmap path** (the default, and ALWAYS on a 1-device plan): the body
  vmaps lanes exactly like ``Solver.solve_batch``. On ``MeshPlan(1, 1)``
  every collective is a singleton identity and no slab padding is
  inserted, so results are bit-identical to the undistributed solver —
  the parity contract ``tests/test_dist_solver.py`` pins down.
* **no-vmap fast path** (multi-device plans with one lane per data
  group): the body runs the loop unbatched. This matters because the
  Pallas entry points are ``custom_vmap``-wrapped with XLA batch rules —
  only the unbatched body keeps the fused kernel pack on the hot path,
  so a pure-pod plan accelerates single solves without giving up the
  kernels.

``DistSolver`` subclasses ``Solver`` and overrides only the two
feasibility primitives; the inherited bound-search driver (``solve``)
is thereby distributed for free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..api.solver import Solver
from ..core.mwu import _run
from ..kernels import dispatch as _kd
from ..launch.mesh import sharding_for
from ..sparsela.partition import partition_edges_1d
from .mesh import POD_AXIS, MeshPlan
from .shard import (
    PodSum,
    SlabCols,
    bounds_spec,
    global_columns,
    pod_mode,
    problem_specs,
    result_specs,
    slab_pad_problem,
)

__all__ = ["DistSolver"]

# jitted shard_map callables keyed on everything static about a launch;
# rebuilding the closure per call would retrace/recompile every time.
_CALLABLE_CACHE: dict = {}


def _build_callable(plan: MeshPlan, opts, kernels, mode, ncols, block, batched, no_vmap, specs):
    """One jitted shard_map program for a (plan, problem-shape) combo."""

    # pod == 1: the wrappers and collectives are mathematical identities,
    # but they still change the emitted HLO enough to perturb XLA fusion
    # rounding — skip them so the traced body is op-for-op the same as
    # ``Solver.solve_batch``'s and 1-device results stay BIT-identical.
    pod_sharded = plan.pod > 1

    def wrap(op):
        if not pod_sharded:
            return op
        if mode == "edge_slab":
            return PodSum(op)
        return SlabCols(op, block=block, n_pod=plan.pod, n_cols=ncols)

    axis = POD_AXIS if pod_sharded else None
    init_cols = ncols if pod_sharded else None

    def one(p, b):
        P, C, pm, cm = p.instantiate(b)
        return _run(
            wrap(P), wrap(C), opts, pm, cm, kernels=kernels, axis=axis, init_cols=init_cols
        )

    if no_vmap:
        # one lane per data group: run the loop unbatched so the Pallas
        # custom_vmap entry points stay on their kernel (not XLA-ref) path.
        def body(problem, bounds):
            p = jax.tree.map(lambda a: a[0], problem) if batched else problem
            res = one(p, bounds[0])
            return jax.tree.map(lambda a: a[None], res)

    else:

        def body(problem, bounds):
            return jax.vmap(one, in_axes=(0 if batched else None, 0))(problem, bounds)

    sharded = plan.shard_map(body, in_specs=(specs, bounds_spec()), out_specs=result_specs())
    return jax.jit(sharded)


class DistSolver(Solver):
    """Mesh-sharded drop-in for :class:`repro.api.Solver`.

    Parameters are ``Solver``'s plus ``plan``, the
    :class:`~repro.dist.mesh.MeshPlan` to launch on.  ``MeshPlan()`` (the
    default) is the 1-device identity plan: every result is bit-identical
    to the plain ``Solver``, so callers can hold a single solver type and
    scale by swapping the plan.

    ``dist_stats`` counts launches / lanes / MWU iterations and (for
    pod-sharded plans) an estimate of psum rounds — 3 collectives per
    iteration (dy, dz, pmax) plus init (y, z, pmin) — surfaced by
    ``repro.lpserve``'s ``stats()``. A launch is counted at the next
    launch or when ``dist_stats`` is read, so ``solve_batch`` never waits
    on the device.
    """

    def __init__(self, opts=None, *, plan: MeshPlan | None = None, **kwargs):
        super().__init__(opts, **kwargs)
        self.plan = plan if plan is not None else MeshPlan()
        self._dist_stats = {
            "launches": 0,
            "feasibility_calls": 0,
            "mwu_iters": 0,
            "psum_rounds": 0,
        }
        self._uncounted = None  # the last launch's iterations, on the device
        # (problem as given, launch key, padded + placed problem, ncols)
        # of the last launch: the bound search's launches share one
        # problem, which is padded and copied to the mesh only once
        self._placed = None

    # -- feasibility primitives (everything else is inherited) ---------
    def _prepare_launch(self, problem, bounds, batched_problem: bool) -> dict:
        """Host-side launch prep shared by execution and AOT inspection.

        Everything up to (but excluding) running the jitted shard_map
        program: pod-mode detection, slab/lane padding, the no-vmap
        decision, kernel-policy resolution, and the callable-cache
        lookup. Returns the padded operands plus the cached callable and
        the static facts (mode, ncols, B) the caller needs afterwards.
        """
        plan = self.plan
        bounds = jnp.atleast_1d(jnp.asarray(bounds))
        B = int(bounds.shape[0])
        mode = pod_mode(problem)
        pad = (-B) % plan.data
        placed = self._placed
        if placed is not None and placed[0] is problem and placed[1] == (batched_problem, pad):
            problem, ncols = placed[2], placed[3]
        else:
            given = problem
            if mode == "edge_slab":
                problem, ncols = slab_pad_problem(problem, plan.pod)
            else:
                ncols = global_columns(problem, np.asarray(bounds)[0], batched_problem)
            if pad and batched_problem:
                problem = jax.tree.map(
                    lambda a: jnp.concatenate(
                        [
                            jnp.asarray(a),
                            jnp.broadcast_to(
                                jnp.asarray(a)[-1:], (pad,) + tuple(jnp.shape(a)[1:])
                            ),
                        ]
                    ),
                    problem,
                )
            if plan.n_devices > 1:
                # each leaf goes where the launch reads it: in edge_slab
                # mode every device holds only its own edge slab
                specs = problem_specs(problem, mode, batched_problem)
                problem = jax.device_put(problem, sharding_for(plan.build(), specs))
            self._placed = (given, (batched_problem, pad), problem, ncols)
        if mode == "edge_slab":
            _, block = partition_edges_1d(ncols, plan.pod)
        else:
            block = -(-ncols // plan.pod)
        if pad:
            bounds = jnp.concatenate([bounds, jnp.broadcast_to(bounds[-1:], (pad,))])
        no_vmap = plan.n_devices > 1 and B + pad == plan.data

        kernels = _kd.resolve(self.opts.kernel_backend)  # host-side, pre-jit
        specs = problem_specs(problem, mode, batched_problem)
        key = (
            plan,
            self.opts,
            kernels,
            mode,
            ncols,
            block,
            batched_problem,
            no_vmap,
            jax.tree_util.tree_structure(problem),
        )
        fn = _CALLABLE_CACHE.get(key)
        if fn is None:
            fn = _build_callable(
                plan, self.opts, kernels, mode, ncols, block, batched_problem, no_vmap, specs
            )
            _CALLABLE_CACHE[key] = fn
        return {
            "problem": problem,
            "bounds": bounds,
            "fn": fn,
            "mode": mode,
            "ncols": ncols,
            "B": B,
            "no_vmap": no_vmap,
        }

    # -- AOT inspection hooks (repro.tracecheck) -----------------------
    def lower_batch(self, problem, bounds, *, batched_problem: bool = False):
        """AOT-lower the mesh-sharded launch this ``solve_batch`` would run."""
        launch = self._prepare_launch(problem, bounds, batched_problem)
        return launch["fn"].lower(launch["problem"], launch["bounds"])

    def jaxpr_batch(self, problem, bounds, *, batched_problem: bool = False):
        """ClosedJaxpr of the mesh-sharded launch (shard_map body visible)."""
        launch = self._prepare_launch(problem, bounds, batched_problem)
        return jax.make_jaxpr(launch["fn"])(launch["problem"], launch["bounds"])

    def solve_batch(self, problem, bounds, *, batched_problem: bool = False):
        """Batched feasibility fanned out over the (pod, data) mesh.

        Same contract as ``Solver.solve_batch``: returns an ``MWUResult``
        with leading dim ``len(bounds)``. Lanes shard over ``data`` (the
        lane count is padded host-side to a multiple of the axis by
        repeating the last lane; padding is stripped before returning),
        each lane's variable space shards over ``pod``.
        """
        launch = self._prepare_launch(problem, bounds, batched_problem)
        problem, bounds, fn = launch["problem"], launch["bounds"], launch["fn"]
        B, ncols = launch["B"], launch["ncols"]

        res = fn(problem, bounds)
        res = jax.tree.map(lambda a: a[:B], res)
        res = res._replace(x=res.x[:, :ncols])

        self._count_launch()
        self._uncounted = res.iters
        return res

    def _count_launch(self) -> None:
        """Add the last launch to ``dist_stats``; by the next launch its
        caller has read it, so this does not wait."""
        if self._uncounted is None:
            return
        iters, self._uncounted = np.asarray(self._uncounted), None
        st = self._dist_stats
        st["launches"] += 1
        st["feasibility_calls"] += iters.size
        st["mwu_iters"] += int(iters.sum())
        if self.plan.pod > 1:
            st["psum_rounds"] += 3 * int(iters.max(initial=0)) + 3

    @property
    def dist_stats(self) -> dict:
        self._count_launch()
        return self._dist_stats

    def feasible(self, problem, bound=None, trace: bool = False):
        """One feasibility solve, pod-sharded when the plan is multi-device.

        Tracing (``trace=True``) stays on the single-device path: the
        io_callback hook is host-side and per-process, so it does not
        compose with shard_map. On a 1-device plan the inherited path is
        also the bit-parity baseline, so it is used directly.
        """
        if trace or self.plan.n_devices == 1:
            return super().feasible(problem, bound, trace=trace)
        b = 1.0 if bound is None else float(bound)
        batch = self.solve_batch(problem, [b])
        return jax.tree.map(lambda a: a[0], batch)
