"""Continuous-batching LP engine: the graph-LP analogue of serve/engine.py.

``serve/engine.py`` batches LM decode across fixed slots; here the unit
of work is one *feasibility probe* of one request's bound search. Each
request is a declarative :class:`~repro.api.Problem`; its binary search
is unrolled into an incremental :class:`BoundSearch` state machine so
the engine can interleave many searches:

1. ``submit`` pads the problem into its shape bucket
   (:mod:`.bucketing`) and enqueues it under a ``(family, bucket)``
   dispatch key;
2. each ``step`` picks the busiest key, refills that bucket's fixed
   lane slots from the queue (continuous batching — free lanes are
   refilled every round, no waiting for a full batch), collects every
   active request's next probe bound, and launches ONE
   ``Solver.solve_batch`` across the stacked lanes;
3. lane results are unpadded back to original variables and fed to each
   request's search; finished requests certify into per-request
   :class:`~repro.api.Solution`s and free their lane.

Because every launch under a dispatch key has identical shapes (slot
count is static; unused lanes re-run a duplicate), XLA compiles once
per key and the jit cache serves every subsequent round —
``stats()["compile_cache_hits"]`` proves it.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..api.problem import Problem
from ..api.solver import (
    Solution,
    Solver,
    certify_solution,
    feasibility_solution,
    new_search_stats,
    not_found_solution,
    stack_problems,
)
from ..core.mwu import MWUOptions, MWUResult, Status
from ..dist.mesh import MeshPlan
from ..dist.solver import DistSolver
from .bucketing import BucketPolicy, BucketSpec, pad_problem, problem_dims
from .stats import BucketStats, aggregate

__all__ = ["LPServeConfig", "LPEngine", "BoundSearch"]


@dataclass(frozen=True)
class LPServeConfig:
    """Engine knobs (frozen so a config can key caches/logs).

    ``mesh`` (a :class:`repro.dist.MeshPlan`, optional) shards each
    dispatch across the device mesh: lane slots fan out over the
    ``data`` axis and each lane's variable space slabs over ``pod``.
    ``None`` keeps the single-device ``Solver`` path bit-for-bit.
    """

    opts: MWUOptions = field(default_factory=MWUOptions)
    lanes: int = 8  # batch slots per dispatch key
    policy: BucketPolicy = field(default_factory=BucketPolicy)
    rel_tol: float | None = None  # bound-search granularity (default eps/2)
    max_calls: int = 64  # per-request feasibility budget
    pad_lanes: bool = True  # always launch the full slot count (shape-static)
    mesh: MeshPlan | None = None  # shard lane slots across this mesh

    def __post_init__(self):
        if self.lanes < 1:
            raise ValueError("lanes must be >= 1")
        if self.mesh is not None and self.lanes % self.mesh.data != 0:
            raise ValueError(
                f"lanes ({self.lanes}) must be a multiple of the mesh data "
                f"axis ({self.mesh.data}) so lane slots shard evenly"
            )


class BoundSearch:
    """Incremental port of ``Solver._bound_search`` (one probe per round).

    ``next_bound`` yields the bound this request wants evaluated;
    ``update`` consumes the (unpadded) feasibility result and advances
    the bracket. ``solution`` is set exactly when the search finishes,
    built by the same certification helpers the sequential solver uses,
    so engine answers are bit-compatible with ``Solver.solve`` at
    ``batch_width=1``.
    """

    def __init__(self, problem: Problem, rel_tol: float, max_calls: int):
        self.problem = problem
        self.rel = rel_tol
        self.max_calls = max_calls
        # launches are shared with other requests: no launch counts
        self.stats = {**new_search_stats(), "batched_iters": None, "launched_lane_iters": None}
        self.best: MWUResult | None = None
        self.best_bound: float | None = None
        self.solution: Solution | None = None
        self.lo = float(problem.lo) if problem.bound_mode != "none" else 0.0
        self.hi = float(problem.hi) if problem.bound_mode != "none" else 0.0
        self.is_max = problem.feasible_side == "lo"
        if problem.bound_mode == "none":
            self.phase = "single"
        elif self.is_max:
            self.phase = "bisect"
            self._maybe_finish()
        else:
            # min-like senses check the easy endpoint first (cheap
            # not-found exit, mirroring the legacy drivers)
            self.phase = "endpoint"

    @property
    def done(self) -> bool:
        return self.solution is not None

    def _bracket_open(self) -> bool:
        return (
            self.hi / max(self.lo, 1e-300) > 1.0 + self.rel
            and self.stats["calls"] < self.max_calls
        )

    def next_bound(self) -> float:
        assert not self.done, "search already finished"
        if self.phase == "single":
            return 1.0  # ignored by bound_mode="none" instantiation
        if self.phase == "endpoint":
            return self.hi
        if self.phase == "final_lo":
            return self.lo
        # geometric midpoint, written exactly as Solver._bound_search's
        # K=1 probe (lo * r ** (1/2)) so probe sequences are bit-identical
        return self.lo * (self.hi / max(self.lo, 1e-300)) ** 0.5

    def update(self, bound: float, res: MWUResult) -> None:
        assert not self.done, "search already finished"
        status = int(res.status)
        ok = status == Status.FEASIBLE
        st = self.stats
        st["calls"] += 1
        st["iters"] += int(res.iters)
        st["probes"] += int(res.ls_probes)
        st["iter_limit"] += int(status == Status.ITER_LIMIT)

        if self.phase == "single":
            self.solution = feasibility_solution(self.problem, res, st)
            return
        if self.phase == "endpoint":
            if not ok:
                self.solution = not_found_solution(self.problem, self.hi, res, st)
                return
            self.best, self.best_bound = res, self.hi
            self.phase = "bisect"
            self._maybe_finish()
            return
        if self.phase == "final_lo":
            if ok:
                self.solution = certify_solution(self.problem, res, self.lo, st)
            else:
                self.solution = not_found_solution(self.problem, self.lo, res, st)
            return
        # bisect: shrink the bracket toward the feasible side
        if self.is_max:
            if ok:
                self.lo, self.best, self.best_bound = bound, res, bound
            else:
                self.hi = bound
        else:
            if ok:
                self.hi, self.best, self.best_bound = bound, res, bound
            else:
                self.lo = bound
        self._maybe_finish()

    def _maybe_finish(self) -> None:
        if self._bracket_open():
            return
        if self.best is None:
            # only reachable for max-sense: lo itself was never probed
            self.phase = "final_lo"
            return
        self.solution = certify_solution(
            self.problem, self.best, self.best_bound, self.stats
        )


@dataclass
class _Request:
    rid: int
    problem: Problem  # original (unpadded) spec
    padded: Problem
    bucket: BucketSpec
    search: BoundSearch
    t_submit: float
    t_done: float | None = None


class _BucketState:
    """Live state of one (family, bucket) dispatch key."""

    def __init__(self, family: str, bucket: BucketSpec):
        self.bucket = bucket
        self.queue: deque[_Request] = deque()
        self.active: list[_Request] = []
        self.stats = BucketStats(family=family, bucket=str(bucket))

    @property
    def backlog(self) -> int:
        return len(self.queue) + len(self.active)


def _jit_cache_size() -> int | None:
    """Entries in the batched-feasibility jit cache (None if unreadable)."""
    from ..api import solver as _solver

    try:
        return int(_solver._feasibility_batch._cache_size())
    except Exception:
        return None


class LPEngine:
    """Shape-bucketed continuous-batching server for graph-LP requests."""

    def __init__(self, config: LPServeConfig | None = None):
        self.cfg = config if config is not None else LPServeConfig()
        if self.cfg.mesh is not None:
            self.solver: Solver = DistSolver(
                self.cfg.opts,
                plan=self.cfg.mesh,
                batch_width=1,
                max_calls=self.cfg.max_calls,
            )
        else:
            self.solver = Solver(self.cfg.opts, batch_width=1, max_calls=self.cfg.max_calls)
        self.rel_tol = (
            self.cfg.rel_tol if self.cfg.rel_tol is not None else self.cfg.opts.eps / 2
        )
        self._buckets: dict[tuple, _BucketState] = {}
        self._done: dict[int, Solution] = {}
        self._requests: dict[int, _Request] = {}
        self._next_rid = 0
        self._seen_shapes: set[tuple] = set()

    # ---------------------------------------------------------- intake --
    def _dispatch_key(self, prob: Problem, bucket: BucketSpec) -> tuple:
        return (prob.name, prob.kind, prob.sense, prob.bound_mode, bucket)

    def submit(self, problem: Problem) -> int:
        """Enqueue one request; returns its request id."""
        n, m = problem_dims(problem)
        bucket = self.cfg.policy.bucket_for(n, m)
        padded = pad_problem(problem, bucket)
        key = self._dispatch_key(problem, bucket)
        state = self._buckets.get(key)
        if state is None:
            state = self._buckets[key] = _BucketState(problem.name, bucket)
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(
            rid=rid,
            problem=problem,
            padded=padded,
            bucket=bucket,
            search=BoundSearch(problem, self.rel_tol, self.cfg.max_calls),
            t_submit=time.perf_counter(),
        )
        self._requests[rid] = req
        state.queue.append(req)
        state.stats.requests += 1
        # a request can be born finished (degenerate bracket, zero probes)
        if req.search.done:
            state.queue.pop()
            self._finish(state, req)
        return rid

    # -------------------------------------------------------- dispatch --
    def _pick_bucket(self) -> _BucketState | None:
        busiest = None
        for state in self._buckets.values():
            if state.backlog and (busiest is None or state.backlog > busiest.backlog):
                busiest = state
        return busiest

    def _finish(self, state: _BucketState, req: _Request) -> None:
        req.t_done = time.perf_counter()
        sol = req.search.solution
        self._done[req.rid] = sol
        state.stats.completed += 1
        state.stats.latencies_s.append(req.t_done - req.t_submit)
        if not sol.found:
            state.stats.not_found += 1

    def step(self) -> bool:
        """One dispatch round on the busiest bucket; False when idle."""
        state = self._pick_bucket()
        if state is None:
            return False
        # continuous batching: refill free lanes from the queue
        while len(state.active) < self.cfg.lanes and state.queue:
            state.active.append(state.queue.popleft())

        real = [(req, req.search.next_bound()) for req in state.active]
        lanes = list(real)
        if self.cfg.pad_lanes:
            while len(lanes) < self.cfg.lanes:  # idle lanes re-run a live probe
                lanes.append(lanes[len(lanes) % len(real)])

        shape_key = (
            self._dispatch_key(lanes[0][0].problem, state.bucket),
            len(lanes),
        )
        # mesh-sharded launches go through repro.dist's own callable
        # cache, not _feasibility_batch's — use the shape-key heuristic.
        cache0 = _jit_cache_size() if self.cfg.mesh is None else None

        stacked = stack_problems([req.padded for req, _ in lanes])
        bounds = jnp.asarray([b for _, b in lanes])
        t0 = time.perf_counter()
        batch = self.solver.solve_batch(stacked, bounds, batched_problem=True)
        jax.block_until_ready(batch.x)
        dt = time.perf_counter() - t0

        cache1 = _jit_cache_size() if self.cfg.mesh is None else None
        if cache0 is not None and cache1 is not None:
            hit = cache1 == cache0
        else:
            hit = shape_key in self._seen_shapes
        self._seen_shapes.add(shape_key)

        st = state.stats
        st.batches += 1
        st.batch_seconds += dt
        st.lane_rounds += len(lanes)
        st.occupied_lane_rounds += len(real)
        st.feasibility_calls += len(real)
        st.compile_cache_hits += int(hit)
        st.compiles += int(not hit)
        for req, _ in real:
            _, m = problem_dims(req.problem)
            st.edge_slots_used += state.bucket.n_edges
            st.real_edges_used += m

        for j, (req, bound) in enumerate(real):
            lane = jax.tree.map(lambda a: a[j], batch)
            res = lane._replace(x=np.asarray(lane.x)[: req.problem.n_vars])
            st.mwu_iters += int(res.iters)
            req.search.update(bound, res)
            if req.search.done:
                self._finish(state, req)
        state.active = [r for r in state.active if not r.search.done]
        return True

    # ------------------------------------------------------- inspection --
    def audit_launches(self) -> dict[tuple, tuple[Problem, jnp.ndarray]]:
        """The (stacked problem, bounds) each dispatch key would launch next.

        For every bucket with backlog, assembles the lanes exactly like
        :meth:`step` — refill simulation, live probe bounds, idle-lane
        duplication, :func:`stack_problems` — WITHOUT mutating any
        engine state (queues, searches and stats are untouched), so
        ``repro.tracecheck`` can lower and lint the real per-key
        programs of a loaded engine. Keyed by the same ``(name, kind,
        sense, bound_mode, bucket)`` dispatch key the jit cache sees.
        """
        out: dict[tuple, tuple[Problem, jnp.ndarray]] = {}
        for key, state in self._buckets.items():
            would_be_active = list(state.active)
            backlog = list(state.queue)
            while len(would_be_active) < self.cfg.lanes and backlog:
                would_be_active.append(backlog.pop(0))
            real = [(req, req.search.next_bound()) for req in would_be_active]
            if not real:
                continue
            lanes = list(real)
            if self.cfg.pad_lanes:
                while len(lanes) < self.cfg.lanes:
                    lanes.append(lanes[len(lanes) % len(real)])
            stacked = stack_problems([req.padded for req, _ in lanes])
            bounds = jnp.asarray([b for _, b in lanes])
            out[key] = (stacked, bounds)
        return out

    # ------------------------------------------------------------ sync --
    def run(self) -> dict[int, Solution]:
        """Drain every pending request; returns {rid: Solution}."""
        while self.step():
            pass
        return dict(self._done)

    def solve_many(self, problems: list[Problem]) -> list[Solution]:
        """Submit + drain a batch; Solutions in submission order."""
        rids = [self.submit(p) for p in problems]
        self.run()
        return [self._done[r] for r in rids]

    def result(self, rid: int) -> Solution | None:
        return self._done.get(rid)

    def stats(self) -> dict:
        """Aggregated serving counters (see :mod:`repro.lpserve.stats`).

        With a mesh-sharded config the dict gains a ``"mesh"`` section:
        the plan shape, per-device lane occupancy (lane rounds divided
        across the ``data`` axis), and the distributed solver's launch /
        psum-round counters.
        """
        out = aggregate(s.stats for s in self._buckets.values())
        plan = self.cfg.mesh
        if plan is not None:
            lane_rounds = sum(s.stats.lane_rounds for s in self._buckets.values())
            ds = dict(self.solver.dist_stats)
            out["mesh"] = {
                "pod": plan.pod,
                "data": plan.data,
                "devices": plan.n_devices,
                "lanes_per_device": self.cfg.lanes // plan.data,
                "lane_rounds_per_device": lane_rounds // plan.data,
                "dist_launches": ds["launches"],
                "psum_rounds": ds["psum_rounds"],
            }
        return out
