"""One driver for every graph LP: bound search with vmap-batched feasibility.

``Solver`` turns a declarative :class:`~repro.api.problem.Problem` into a
:class:`Solution` by reducing optimization to feasibility (paper §2.2)
and searching the objective bound. Two execution modes:

* ``batch_width == 1`` — the paper's sequential geometric binary search,
  one jitted feasibility solve per probe (exactly the legacy
  ``core.feasibility`` drivers).
* ``batch_width K > 1`` — speculative bracket evaluation (DESIGN.md §5
  note): each round instantiates K candidate bounds and ``jax.vmap``s
  the MWU ``lax.while_loop`` across them in ONE XLA call, shrinking the
  bracket by ~(K+1)x per round instead of 2x. The parallel-LP analogue
  of Allen-Zhu & Orecchia / Wang et al.'s width-parallelism.

``solve_batch`` exposes the raw fan-out: batched ``MWUResult`` across an
array of bounds, optionally also across stacked same-shape graph
instances (``stack_problems``).

Each ``Solver.solve`` is a tree of host spans, written to the JAX
profiler's trace as ``TraceAnnotation`` events tagged with the problem's
name and the search round: ``solver.solve`` holds one ``solver.round`` per
probe round and a closing ``solver.certify``; a round holds
``solver.dispatch`` (the launch), ``solver.wait`` (the first host read of
the launch's statuses, where the host blocks on the device) and
``solver.readback`` (per-lane results and counts). Their self times, in
host seconds, are ``Solution.timings``.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core import mwu as _mwu
from ..core.mwu import MWUOptions, MWUResult, Status, _run, solve, solve_traced
from ..kernels import dispatch as _kd
from .problem import Problem

__all__ = [
    "Solution",
    "Solver",
    "stack_problems",
    "feasibility_solution",
    "not_found_solution",
    "certify_solution",
]


@dataclass
class Solution:
    """Unified result of a ``Solver`` run.

    ``objective`` is the certified value of ``x`` after the (1+eps)
    rescale (max: divide by packing overshoot; min: exploit covering
    slack); for densest-subgraph it is the certified density bound.
    ``trace`` (optional) is a list of per-feasibility-call dicts from the
    io_callback trace hook, each with the probed ``bound`` plus the
    ``max_violation`` / ``alpha`` / ``probes`` arrays of Figure 3.

    Counts of the search's launches: ``iter_limit_lanes`` lanes ended
    ``ITER_LIMIT``; ``batched_iters`` is the sum over launches of their
    slowest lane's iterations (a vmapped loop runs every lane that long)
    and ``launched_lane_iters`` the sum of lanes x slowest lane, so
    ``mwu_iters_total / launched_lane_iters`` is the share of launched
    lane-iterations a lane needed. ``timings`` holds the host seconds of
    ``Solver.solve`` by span (``dispatch``, ``wait``, ``readback``,
    ``certify``, and ``search`` for the rest); they sum to the solve's
    wall time. A solution of ``repro.lpserve``'s engine shares each launch
    with other requests, so there the two launch counts and ``timings``
    are None.
    """

    problem: str
    status: int  # core Status code of the certifying solve
    x: np.ndarray | None  # best feasible solution (original variables)
    objective: float
    bound: float  # final binary-search bound
    max_px: float  # certificates at exit
    min_cx: float
    feasibility_calls: int
    mwu_iters_total: int
    ls_probes_total: int
    last_result: MWUResult | None = None
    trace: list | None = None
    iter_limit_lanes: int = 0
    batched_iters: int | None = None
    launched_lane_iters: int | None = None
    timings: dict | None = None

    @property
    def found(self) -> bool:
        return self.x is not None

    @property
    def feasible(self) -> bool:
        return self.status == Status.FEASIBLE and self.found


@partial(jax.jit, static_argnames=("opts", "problem_axis", "kernels"))
def _feasibility_batch(problem: Problem, bounds, opts: MWUOptions, problem_axis, kernels=None):
    """vmap the MWU while_loop across bounds (and optionally instances).

    ``kernels`` is the host-resolved KernelPolicy (static): pallas entry
    points are ``custom_vmap``-wrapped, so batched lanes transparently
    take the vmap-composable XLA rule while the policy still keys the
    jit cache consistently with the unbatched path.
    """

    def one(prob, b):
        P, C, pm, cm = prob.instantiate(b)
        return _run(P, C, opts, pm, cm, kernels=kernels)

    return jax.vmap(one, in_axes=(problem_axis, 0))(problem, bounds)


def _check_stackable(problems: list[Problem]) -> None:
    """Raise a ValueError naming the first mismatched aux field / leaf."""
    ref = problems[0]
    ref_flat, ref_tree = jax.tree_util.tree_flatten_with_path(ref)
    for i, p in enumerate(problems[1:], start=1):
        if isinstance(ref, Problem) and isinstance(p, Problem):
            for f in ("name", "kind", "sense", "bound_mode", "n_vars", "nnz", "make_ops"):
                a, b = getattr(ref, f), getattr(p, f)
                if a != b:
                    raise ValueError(
                        f"stack_problems: problem 0 and problem {i} differ in "
                        f"static field {f!r}: {a!r} vs {b!r}; only problems of "
                        "the same family can be instance-batched"
                    )
        flat, tree = jax.tree_util.tree_flatten_with_path(p)
        if tree != ref_tree:
            keys0 = {jax.tree_util.keystr(k) for k, _ in ref_flat}
            keys = {jax.tree_util.keystr(k) for k, _ in flat}
            diff = sorted(keys0.symmetric_difference(keys)) or ["<nested structure>"]
            raise ValueError(
                f"stack_problems: problem 0 and problem {i} have different "
                f"pytree structure (mismatched leaves: {', '.join(diff)}); "
                "pad differently-shaped problems into a common bucket first "
                "(repro.lpserve.pad_problems)"
            )
        for (key, leaf0), (_, leaf) in zip(ref_flat, flat):
            s0, s = jnp.shape(leaf0), jnp.shape(leaf)
            if s0 != s:
                raise ValueError(
                    f"stack_problems: leaf {jax.tree_util.keystr(key)!r} has "
                    f"shape {s} in problem {i} but {s0} in problem 0; pad "
                    "differently-sized graphs into a common shape bucket "
                    "first (repro.lpserve.pad_problems)"
                )


def stack_problems(problems: list[Problem]) -> Problem:
    """Tree-stack same-shape Problems for instance-batched ``solve_batch``.

    All problems must share pytree structure and leaf shapes (same
    vertex/edge counts — pad into a shape bucket with
    :func:`repro.lpserve.pad_problems` when they differ). Mismatches
    raise a ``ValueError`` naming the offending field or leaf.
    """
    if not problems:
        raise ValueError("stack_problems: need at least one problem")
    _check_stackable(list(problems))
    return jax.tree.map(lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]), *problems)


class _Spans:
    """The host spans of one ``Solver.solve`` and their self times.

    ``spans(name, key)`` is a ``solver.<name>`` profiler event tagged with
    the problem and the current search round; on exit its duration less
    that of the spans opened inside it adds to ``times[key]``.
    """

    def __init__(self, problem: str):
        self.problem, self.round = problem, 0
        self.times = dict.fromkeys(("dispatch", "wait", "readback", "certify", "search"), 0.0)
        self._inner = [0.0]  # seconds in child spans, one entry per open span

    @contextmanager
    def __call__(self, name: str, key: str = "search"):
        self._inner.append(0.0)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"solver.{name}", problem=self.problem, round=self.round):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.times[key] += dt - self._inner.pop()
            self._inner[-1] += dt


def new_search_stats() -> dict:
    """A search's counts; ``Solution`` takes them over (``_counts``)."""
    return {"calls": 0, "iters": 0, "probes": 0, "iter_limit": 0, "batched_iters": 0, "launched_lane_iters": 0}


def _count_launch(stats: dict, status: np.ndarray, iters: np.ndarray, probes: np.ndarray) -> None:
    """Add one launch's lanes (host arrays, one entry per lane) to ``stats``."""
    lanes, slowest = status.size, int(iters.max(initial=0))
    stats["calls"] += lanes
    stats["iters"] += int(iters.sum())
    stats["probes"] += int(probes.sum())
    stats["iter_limit"] += int(np.sum(status == Status.ITER_LIMIT))
    stats["batched_iters"] += slowest
    stats["launched_lane_iters"] += lanes * slowest


class Solver:
    """The public facade: Problem in, Solution out.

    Parameters
    ----------
    opts:        core MWU configuration (eps, step rule, iteration cap).
    batch_width: feasibility probes evaluated per search round in one
                 vmapped XLA call; 1 reproduces the paper's sequential
                 binary search.
    rel_tol:     bound-search granularity (default eps/2, so the search
                 does not compound the solver's eps past the paper's
                 acceptance band).
    max_calls:   total feasibility-solve budget per ``solve``.
    """

    def __init__(
        self,
        opts: MWUOptions | None = None,
        *,
        batch_width: int = 4,
        rel_tol: float | None = None,
        max_calls: int = 64,
    ):
        self.opts = opts if opts is not None else MWUOptions()
        if batch_width < 1:
            raise ValueError("batch_width must be >= 1")
        self.batch_width = int(batch_width)
        self.rel_tol = rel_tol
        self.max_calls = int(max_calls)

    # -- feasibility primitives ---------------------------------------
    def feasible(self, problem: Problem, bound=None, trace: bool = False):
        """One feasibility solve at a concrete bound.

        Returns ``MWUResult`` (or ``(MWUResult, trace_dict)`` with
        ``trace=True``). Instantiates the operators host-side so the
        core jit cache is keyed on operator structure, not on the bound.
        """
        P, C, pm, cm = problem.instantiate(bound)
        if trace:
            return solve_traced(P, C, self.opts, p_mask=pm, c_mask=cm)
        return solve(P, C, self.opts, p_mask=pm, c_mask=cm)

    def solve_batch(self, problem: Problem, bounds, *, batched_problem: bool = False) -> MWUResult:
        """Batched feasibility: vmap the MWU loop across ``bounds``.

        One XLA call evaluates every bound concurrently (speculative
        bracket evaluation). With ``batched_problem=True``, ``problem``
        must carry a leading batch axis on every leaf (see
        :func:`stack_problems`) matching ``bounds`` — fan-out across
        independent graph instances.

        Returns an ``MWUResult`` whose every field has leading dim
        ``len(bounds)``.
        """
        bounds = jnp.atleast_1d(jnp.asarray(bounds))
        kernels = _kd.resolve(self.opts.kernel_backend)  # host-side, pre-jit
        return _feasibility_batch(
            problem, bounds, self.opts, 0 if batched_problem else None, kernels=kernels
        )

    # -- AOT inspection hooks (repro.tracecheck) -----------------------
    # Same jit entries / statics / host-side resolution as the executing
    # paths above, so the linted program is the program a call would run.
    def lower_feasible(self, problem: Problem, bound=None, *, trace: bool = False):
        """AOT-lower one :meth:`feasible` call (``jax.stages.Lowered``)."""
        P, C, pm, cm = problem.instantiate(bound)
        return _mwu.lower(P, C, self.opts, p_mask=pm, c_mask=cm, trace=trace)

    def jaxpr_feasible(self, problem: Problem, bound=None, *, trace: bool = False):
        """ClosedJaxpr of one :meth:`feasible` call (primitive-level view)."""
        P, C, pm, cm = problem.instantiate(bound)
        return _mwu.solve_jaxpr(P, C, self.opts, p_mask=pm, c_mask=cm, trace=trace)

    def lower_batch(self, problem: Problem, bounds, *, batched_problem: bool = False):
        """AOT-lower one :meth:`solve_batch` call without executing it."""
        bounds = jnp.atleast_1d(jnp.asarray(bounds))
        kernels = _kd.resolve(self.opts.kernel_backend)
        return _feasibility_batch.lower(
            problem, bounds, self.opts, 0 if batched_problem else None, kernels=kernels
        )

    def jaxpr_batch(self, problem: Problem, bounds, *, batched_problem: bool = False):
        """ClosedJaxpr of one :meth:`solve_batch` call."""
        bounds = jnp.atleast_1d(jnp.asarray(bounds))
        kernels = _kd.resolve(self.opts.kernel_backend)
        axis = 0 if batched_problem else None
        fn = _feasibility_batch.__wrapped__

        def call(p, b):
            return fn(p, b, self.opts, axis, kernels=kernels)

        return jax.make_jaxpr(call)(problem, bounds)

    # -- the unified optimization driver ------------------------------
    def solve(self, problem: Problem, *, trace: bool = False) -> Solution:
        """Optimize ``problem`` via bound search over feasibility calls."""
        spans = _Spans(problem.name)
        with spans("solve"):
            if problem.bound_mode == "none":
                sol = self._solve_feasibility(problem, trace, spans)
            else:
                sol = self._bound_search(problem, trace, spans)
        sol.timings = spans.times
        return sol

    # pure feasibility problems skip the search entirely
    def _solve_feasibility(self, problem: Problem, trace: bool, spans: _Spans) -> Solution:
        traces = [] if trace else None
        stats = new_search_stats()
        (_, res), = self._probe(problem, [None], trace, traces, stats, spans)
        with spans("certify", "certify"):
            return feasibility_solution(problem, res, stats, traces)

    def _probe(self, problem, bounds, trace, traces, stats, spans):
        """Evaluate feasibility at each bound: one search round.

        Batched into one launch when the width allows, else one launch
        per bound. The lanes' statuses, iterations and probes are read
        back once per launch.
        """
        outs = []
        with spans("round"):
            if len(bounds) > 1 and not trace:
                with spans("dispatch", "dispatch"):
                    batch = self.solve_batch(problem, jnp.asarray(bounds))
                with spans("wait", "wait"):
                    status = np.asarray(batch.status)
                with spans("readback", "readback"):
                    for j, ok in enumerate(status == Status.FEASIBLE):
                        outs.append((bool(ok), jax.tree.map(lambda a: a[j], batch)))
                    _count_launch(stats, status, np.asarray(batch.iters), np.asarray(batch.ls_probes))
            else:
                for b in bounds:
                    with spans("dispatch", "dispatch"):
                        if trace:
                            res, tr = self.feasible(problem, b, trace=True)
                            traces.append(dict(bound=float("nan") if b is None else float(b), **tr))
                        else:
                            res = self.feasible(problem, b)
                    with spans("wait", "wait"):
                        status = np.atleast_1d(np.asarray(res.status))
                    with spans("readback", "readback"):
                        outs.append((int(status[0]) == Status.FEASIBLE, res))
                        _count_launch(stats, status, np.atleast_1d(np.asarray(res.iters)),
                                      np.asarray(res.ls_probes))
        spans.round += 1
        return outs

    def _bound_search(self, problem: Problem, trace: bool, spans: _Spans) -> Solution:
        is_max = problem.feasible_side == "lo"
        lo, hi = float(problem.lo), float(problem.hi)
        rel = self.rel_tol if self.rel_tol is not None else self.opts.eps / 2
        K = 1 if trace else self.batch_width
        stats = new_search_stats()
        traces: list = [] if trace else None
        best = best_bound = None

        def probe(bounds):
            return self._probe(problem, bounds, trace, traces, stats, spans)

        # min-like senses: the feasible side is hi; the legacy drivers
        # check it up front and bail immediately when even hi fails.
        # (With K > 1 the endpoint could ride along in round 1's batch,
        # but checking it alone first keeps the not-found exit cheap.)
        if not is_max:
            (ok, res), = probe([hi])
            if not ok:
                return self._not_found(problem, hi, res, stats, traces, spans)
            best, best_bound = res, hi

        first = True
        while hi / max(lo, 1e-300) > 1.0 + rel and stats["calls"] < self.max_calls:
            r = hi / max(lo, 1e-300)
            if first and is_max and K > 1:
                # fold the feasible-side endpoint lo into round 1's batch
                pts = [lo * r ** (k / K) for k in range(K)]
            else:
                pts = [lo * r ** (k / (K + 1)) for k in range(1, K + 1)]
            outs = probe(pts)
            feas = [ok for ok, _ in outs]
            if is_max:
                # feasible for small bounds: push lo up to the largest
                # feasible probe, pull hi down to the smallest infeasible.
                f_idx = [i for i, ok in enumerate(feas) if ok]
                if f_idx:
                    j = f_idx[-1]
                    lo, best, best_bound = pts[j], outs[j][1], pts[j]
                else:
                    if first and K > 1:  # round 1 included lo itself
                        return self._not_found(problem, lo, outs[0][1], stats, traces, spans)
                i_idx = [i for i, ok in enumerate(feas) if not ok]
                if i_idx:
                    hi = pts[i_idx[0]]
            else:
                # feasible for large bounds: mirror image
                f_idx = [i for i, ok in enumerate(feas) if ok]
                if f_idx:
                    j = f_idx[0]
                    hi, best, best_bound = pts[j], outs[j][1], pts[j]
                i_idx = [i for i, ok in enumerate(feas) if not ok]
                if i_idx:
                    lo = pts[i_idx[-1]]
            first = False

        if best is None:  # only reachable for sense="max" (lo never probed)
            (ok, res), = probe([lo])
            if not ok:
                return self._not_found(problem, lo, res, stats, traces, spans)
            best, best_bound = res, lo

        return self._certify(problem, best, best_bound, stats, traces, spans)

    def _not_found(self, problem, bound, res, stats, traces, spans) -> Solution:
        with spans("certify", "certify"):
            return not_found_solution(problem, bound, res, stats, traces)

    def _certify(self, problem, best, best_bound, stats, traces, spans) -> Solution:
        with spans("certify", "certify"):
            return certify_solution(problem, best, best_bound, stats, traces)


# -- Solution construction (shared with repro.lpserve's engine) -----------
def _counts(stats) -> dict:
    """Solution's counts from a search's ``stats`` (``new_search_stats``)."""
    return dict(
        feasibility_calls=stats["calls"],
        mwu_iters_total=stats["iters"],
        ls_probes_total=stats["probes"],
        iter_limit_lanes=stats["iter_limit"],
        batched_iters=stats["batched_iters"],
        launched_lane_iters=stats["launched_lane_iters"],
    )


def feasibility_solution(problem, res, stats, traces=None) -> Solution:
    """Solution for a single feasibility solve (``bound_mode="none"``)."""
    ok = int(res.status) == Status.FEASIBLE
    return Solution(
        problem=problem.name,
        status=int(res.status),
        x=np.asarray(res.x) if ok else None,
        objective=float("nan"),
        bound=float("nan"),
        max_px=float(res.max_px),
        min_cx=float(res.min_cx),
        **_counts(stats),
        last_result=res,
        trace=traces,
    )


def not_found_solution(problem, bound, res, stats, traces=None) -> Solution:
    """Solution reporting that even the easy endpoint bound was infeasible."""
    return Solution(
        problem=problem.name,
        status=int(res.status),
        x=None,
        objective=0.0,
        bound=float(bound),
        max_px=float(res.max_px),
        min_cx=float(res.min_cx),
        **_counts(stats),
        last_result=res,
        trace=traces,
    )


def certify_solution(problem, best, best_bound, stats, traces=None) -> Solution:
    """Rescale the raw MWU point into a certified solution (§2.2)."""
    x = np.asarray(best.x)
    if problem.sense == "max":
        # Px <= 1+eps: dividing by the overshoot certifies Px <= 1
        # at an objective loss of at most (1+eps).
        x = x / max(float(best.max_px), 1.0)
        objective = float(np.dot(np.asarray(problem.c), x))
    elif problem.bound_mode == "objective_packing":
        # covering slack is free objective: x/min(Cx) stays feasible
        x = x / max(float(best.min_cx), 1.0)
        objective = float(np.dot(np.asarray(problem.c), x))
    else:
        # densest-style: the bound itself is the certified objective
        objective = float(best_bound)
    return Solution(
        problem=problem.name,
        status=int(best.status),
        x=x,
        objective=objective,
        bound=float(best_bound),
        max_px=float(best.max_px),
        min_cx=float(best.min_cx),
        **_counts(stats),
        last_result=best,
        trace=traces,
    )
