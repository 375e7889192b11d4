"""MWU solver for mixed packing & covering LPs (paper Algorithms 1-2).

Feasibility problem (paper eq. 2):

    exists x >= 0  with  P x <= 1  and  C x >= 1,

P, C entrywise nonnegative ``LinOp``s. The solver returns a
(1+eps)-relative solution (P x <= (1+eps) 1, C x >= 1) or reports
INFEASIBLE, in O~(eps^-3) iterations (eps^-2 for pure problems).

One trace-unified driver serves every entry point: a single
``lax.while_loop`` (the whole solve is one XLA program; all vector work
between the two SpMVs of an iteration fuses, which is the XLA analogue
of the paper's §5.1.3 loop fusion) with an optional ``io_callback``
trace hook that streams per-iteration diagnostics (max violation, alpha,
probes) to the host for the Figure-3 convergence studies.

``MWUOptions.kernel_backend`` selects the vector-op implementation for
the loop body: under ``"pallas"`` the incidence gathers (interpret mode
only), the eta-softmax gradient weights, every line-search probe, and
the x/y/z update triple run through the fused Pallas kernel pack via
``repro.kernels.dispatch`` — the entry points resolve the backend
host-side (outside jit) into a :class:`~repro.kernels.dispatch.KernelPolicy`
static argument, so the jit cache can never serve a stale device choice,
and CPU runs exercise the identical kernel code in interpret mode.

* ``solve``        — the production path (trace hook off).
* ``solve_traced`` — same compiled loop with the trace hook on; kept as
                     a thin shim for legacy callers. The canonical
                     public surface is :mod:`repro.api` (``Solver`` /
                     ``Problem``), which also vmaps this driver across
                     binary-search bounds and graph instances.

State kept across iterations (paper Alg. 2 lines 3, 10, 15): x and the
constraint images y = Px, z = Cx and step images d_y = Pd, d_z = Cd, so
each iteration performs exactly two pairs of SpMVs (P/Pᵀ, C/Cᵀ) — never
recomputing Px from scratch.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import dispatch as _kd
from .operators import LinOp, with_endpoint_order
from .smoothing import smax_and_weights, smin_and_weights
from .stepsize import STEP_RULES, StepSizeResult

__all__ = [
    "MWUOptions",
    "MWUResult",
    "Status",
    "solve",
    "solve_traced",
    "lower",
    "solve_jaxpr",
    "init_x",
    "make_eta",
]


class Status:
    RUNNING = 0
    FEASIBLE = 1
    INFEASIBLE = 2
    ITER_LIMIT = 3

    NAMES = {0: "RUNNING", 1: "FEASIBLE", 2: "INFEASIBLE", 3: "ITER_LIMIT"}


@dataclass(frozen=True)
class MWUOptions:
    """Static solver configuration (hashable -> usable as jit static arg)."""

    eps: float = 0.1
    max_iter: int = 5000  # paper §6.2
    step_rule: str = "newton"  # "std" | "binary" | "newton"
    ls_eps: float | None = None  # line-search relative tolerance (default: eps)
    eta_factor: float = 10.0  # eta = eta_factor * log(m) / eps (paper line 2)
    pure: bool | None = None  # None = auto-detect single-row objective embedding
    # packing slack accepted at termination; the theory gives (1+eps).
    check_packing: bool = True
    # vector-op backend for the loop body: "auto" (xla everywhere until a
    # chip measurement shows a kernel winning; REPRO_KERNEL_BACKEND env
    # var overrides), "pallas" (fused kernel pack, interpret mode
    # off-TPU), or "xla".
    kernel_backend: str = "auto"

    def resolve_pure(self, P: LinOp, C: LinOp) -> bool:
        if self.pure is not None:
            return self.pure
        return P.shape[0] == 1 or C.shape[0] == 1

    @property
    def ls_tol(self) -> float:
        return self.eps if self.ls_eps is None else self.ls_eps


class MWUResult(NamedTuple):
    x: jax.Array
    status: jax.Array  # int32 Status code
    iters: jax.Array  # MWU iterations executed
    ls_probes: jax.Array  # total line-search probes (Table 3)
    max_px: jax.Array  # max_i (Px)_i at exit
    min_cx: jax.Array  # min_i (Cx)_i at exit

    @property
    def feasible(self):
        return self.status == Status.FEASIBLE


def make_eta(m: int, eps: float, eta_factor: float = 10.0):
    return eta_factor * np.log(max(m, 2)) / eps


def init_x(P: LinOp, eps: float, dtype, n_cols: int | None = None, axis=None) -> jax.Array:
    """x_i = eps / (n * ||P_{:,i}||_inf)  (paper Alg. 1 line 3).

    Guarantees every packing row starts at most eps. Columns absent from P
    (colmax = 0) would start unbounded; they are clamped to the max of the
    present columns' scale (only well-posed LPs reach us in practice).

    ``n_cols`` overrides the column count when ``P`` is a per-device
    shard of a wider operator (repro.dist slab sharding), so the init
    scale matches the single-device solve; ``axis`` names the mesh axis
    the fallback min must reduce over in that case.
    """
    n = P.shape[1] if n_cols is None else n_cols
    cm = P.colmax().astype(dtype)
    safe = jnp.where(cm > 0, cm, jnp.inf)
    x = eps / (n * safe)
    fallback = jnp.min(jnp.where(cm > 0, x, jnp.inf))
    if axis is not None:
        fallback = jax.lax.pmin(fallback, axis)
    fallback = jnp.where(jnp.isfinite(fallback), fallback, eps / n)
    return jnp.where(cm > 0, x, fallback).astype(dtype)


class _Carry(NamedTuple):
    x: jax.Array
    y: jax.Array
    z: jax.Array
    it: jax.Array
    probes: jax.Array
    alpha_prev: jax.Array
    status: jax.Array


def _masked_min(v, mask):
    return jnp.min(v) if mask is None else jnp.min(jnp.where(mask, v, jnp.inf))


def _masked_max(v, mask):
    return jnp.max(v) if mask is None else jnp.max(jnp.where(mask, v, -jnp.inf))


def _iteration(P: LinOp, C: LinOp, eta, scale, step_fn, ls_eps, p_mask, c_mask, axis, carry: _Carry) -> _Carry:
    """One MWU iteration (Alg. 2 body). Returns the updated carry.

    ``axis`` (a mesh axis name or None) marks an SPMD run where the
    variable space is slab-sharded across that axis (repro.dist): the
    only variable-space *global* reduction in the body — the
    infeasible-direction test on ``max(d)`` — then psum-completes via
    ``lax.pmax``. Constraint-space vectors (y, z, dy, dz) stay
    replicated across the axis (the sharded operators psum their
    matvec outputs), so the smoothing/step-size math needs no change.
    """
    x, y, z = carry.x, carry.y, carry.z
    dt = x.dtype
    tiny = jnp.asarray(jnp.finfo(dt).tiny, dt)

    # gradients of the smoothed constraint potentials (lines 5-6)
    _, wp = smax_and_weights(y, eta, where=p_mask)
    _, wc = smin_and_weights(z, eta, where=c_mask)
    g = P.rmatvec(wp)  # packing gradient  P^T grad smax(Px)
    h = C.rmatvec(wc)  # covering gradient C^T grad smin(Cx)

    # step direction (line 7): d_i = scale * max(0, 1 - g_i/h_i) * x_i
    ratio = jnp.where(h > tiny, g / jnp.maximum(h, tiny), jnp.inf)
    d = scale * jnp.maximum(0.0, 1.0 - ratio) * x

    max_d = jnp.max(d)
    if axis is not None:
        max_d = jax.lax.pmax(max_d, axis)
    infeasible_dir = max_d <= 0  # line 8

    # step images (line 10) — the second SpMV pair
    dy = P.matvec(d)
    dz = C.matvec(d)

    # step size (line 11)
    with jax.named_scope("mwu.linesearch"):
        ss: StepSizeResult = step_fn(y, z, dy, dz, eta, p_mask, c_mask, ls_eps, carry.alpha_prev)
    infeasible_alpha = ss.alpha < 1  # line 12

    # apply (lines 14-15); never move on a terminal iteration. Under a
    # pallas policy the update triple runs as fused axpy+reduce sweeps
    # (the min/max come free; XLA DCEs them on the fallback path).
    bad = infeasible_dir | infeasible_alpha
    aa = jnp.where(bad, 0.0, ss.alpha).astype(dt)
    if _kd.choose("axpy", x) == "pallas":
        x2, _, _ = _kd.axpy_pallas(x, d, aa)
        y2, _, _ = _kd.axpy_pallas(y, dy, aa)
        z2, _, _ = _kd.axpy_pallas(z, dz, aa)
    else:
        x2 = x + aa * d
        y2 = y + aa * dy
        z2 = z + aa * dz

    status = jnp.where(
        infeasible_dir | infeasible_alpha,
        jnp.int32(Status.INFEASIBLE),
        jnp.int32(Status.RUNNING),
    )
    return _Carry(
        x=x2,
        y=y2,
        z=z2,
        it=carry.it + 1,
        probes=carry.probes + ss.probes,
        alpha_prev=jnp.where(bad, carry.alpha_prev, ss.alpha.astype(dt)),
        status=status,
    )


def _finalize(opts: MWUOptions, carry: _Carry, p_mask, c_mask) -> MWUResult:
    max_px = _masked_max(carry.y, p_mask)
    min_cx = _masked_min(carry.z, c_mask)
    covered = min_cx >= 1.0
    packed = (max_px <= 1.0 + opts.eps + 1e-9) | (not opts.check_packing)
    status = jnp.where(
        carry.status == Status.INFEASIBLE,
        jnp.int32(Status.INFEASIBLE),
        jnp.where(
            covered & packed,
            jnp.int32(Status.FEASIBLE),
            jnp.int32(Status.ITER_LIMIT),
        ),
    )
    return MWUResult(
        x=carry.x,
        status=status,
        iters=carry.it,
        ls_probes=carry.probes,
        max_px=max_px,
        min_cx=min_cx,
    )


class _TraceSink:
    """Host-side accumulator fed by the in-loop ``io_callback`` hook.

    Rows are (iteration, violation, alpha, probes) tuples; the iteration
    index makes row order irrelevant, so the callback can stay unordered
    (ordered effects are not supported inside ``lax.while_loop``).
    Not thread-safe: one traced solve at a time.
    """

    def __init__(self):
        self.rows: list | None = None


_TRACE = _TraceSink()


def _trace_emit(it, viol, alpha, probes):
    if _TRACE.rows is not None:
        _TRACE.rows.append((int(it), float(viol), float(alpha), int(probes)))


def _run(
    P: LinOp,
    C: LinOp,
    opts: MWUOptions,
    pm,
    cm,
    trace: bool = False,
    kernels=None,
    axis=None,
    init_cols=None,
):
    """The unified driver: one ``lax.while_loop`` for jit, vmap and tracing.

    Masks are None-or-array at the python level (callers that need a
    pytree-stable jit signature pass dummies through ``_solve_impl``).
    With ``trace=True`` each iteration emits (it, violation, alpha,
    probes) through an unordered ``io_callback`` into ``_TRACE``; the
    hook must stay off under ``jax.vmap`` (io_callback has no batching
    rule by default), which ``repro.api`` enforces.

    ``kernels`` is the resolved :class:`~repro.kernels.dispatch.KernelPolicy`
    installed for the duration of this trace; the public entry points
    resolve it host-side and pass it through as a jit static argument.
    Direct callers that omit it get a trace-time resolution fallback.

    ``axis``/``init_cols`` are set only by :mod:`repro.dist` when the
    variable space is slab-sharded across a mesh axis: ``axis`` names
    the axis for the two variable-space collectives (init fallback min,
    infeasible-direction max), ``init_cols`` is the *global* column
    count so the init scale matches the single-device solve.
    """
    policy = kernels if kernels is not None else _kd.resolve(opts.kernel_backend)
    with _kd.use_policy(policy):
        return _run_inner(P, C, opts, pm, cm, trace, axis, init_cols)


def _run_inner(P: LinOp, C: LinOp, opts: MWUOptions, pm, cm, trace: bool, axis=None, init_cols=None):
    # sort the scatter direction's endpoints once per launch, not per iteration
    P, C = with_endpoint_order(P), with_endpoint_order(C)
    m = P.shape[0] + C.shape[0]
    dt = jnp.promote_types(P.colmax().dtype, C.colmax().dtype)
    dt = dt if jnp.issubdtype(dt, jnp.floating) else jnp.float32
    eta = jnp.asarray(make_eta(m, opts.eps, opts.eta_factor), dt)
    # pure packing/covering admit a 2x larger step scale (paper §2.2)
    scale = (1.0 if opts.resolve_pure(P, C) else 0.5) / eta
    step_fn = STEP_RULES[opts.step_rule]

    x0 = init_x(P, opts.eps, dt, n_cols=init_cols, axis=axis)
    carry0 = _Carry(
        x=x0,
        y=P.matvec(x0).astype(dt),
        z=C.matvec(x0).astype(dt),
        it=jnp.zeros((), jnp.int32),
        probes=jnp.zeros((), jnp.int32),
        alpha_prev=jnp.ones((), dt),
        status=jnp.int32(Status.RUNNING),
    )

    def cond(carry: _Carry):
        done_cover = _masked_min(carry.z, cm) >= 1.0
        return (
            (carry.status == Status.RUNNING)
            & (~done_cover)
            & (carry.it < opts.max_iter)
        )

    iter_body = partial(_iteration, P, C, eta, scale, step_fn, opts.ls_tol, pm, cm, axis)

    if trace:
        from jax.experimental import io_callback

        def body(carry: _Carry) -> _Carry:
            nxt = iter_body(carry)
            viol = jnp.maximum(
                jnp.maximum(_masked_max(carry.y, pm) - 1.0, 1.0 - _masked_min(carry.z, cm)),
                0.0,
            )
            io_callback(_trace_emit, None, carry.it, viol, nxt.alpha_prev, nxt.probes - carry.probes)
            return nxt

    else:
        body = iter_body

    carry = jax.lax.while_loop(cond, body, carry0)
    return _finalize(opts, carry, pm, cm)


@partial(jax.jit, static_argnames=("opts", "has_p_mask", "has_c_mask", "trace", "kernels"))
def _solve_impl(P, C, opts: MWUOptions, p_mask, c_mask, has_p_mask, has_c_mask, trace=False, kernels=None):
    pm = p_mask if has_p_mask else None
    cm = c_mask if has_c_mask else None
    return _run(P, C, opts, pm, cm, trace=trace, kernels=kernels)


def _mask_args(P, C, p_mask, c_mask):
    """Dummy-mask plumbing shared by solve / solve_traced / lower.

    Masks are passed as dummies when absent so the jit signature stays
    pytree-stable; the has_* statics select whether they are real.
    """
    hp, hc = p_mask is not None, c_mask is not None
    pm = p_mask if hp else jnp.zeros((P.shape[0],), bool)
    cmk = c_mask if hc else jnp.zeros((C.shape[0],), bool)
    return pm, cmk, hp, hc


def solve(P: LinOp, C: LinOp, opts: MWUOptions = MWUOptions(), p_mask=None, c_mask=None) -> MWUResult:
    """Solve the feasibility LP  P x <= 1, C x >= 1, x >= 0  (fully jitted)."""
    pm, cmk, hp, hc = _mask_args(P, C, p_mask, c_mask)
    # Resolve the kernel backend OUTSIDE the jit: the concrete policy is
    # part of the cache key, so a device switch re-resolves instead of
    # serving a stale trace-time jax.default_backend() read.
    kernels = _kd.resolve(opts.kernel_backend)
    return _solve_impl(P, C, opts, pm, cmk, hp, hc, kernels=kernels)


def lower(P: LinOp, C: LinOp, opts: MWUOptions = MWUOptions(), p_mask=None, c_mask=None, trace=False):
    """AOT-lower :func:`solve` without executing it (``jax.stages.Lowered``).

    Same jit entry, statics and dummy-mask plumbing as :func:`solve`, so
    what ``repro.tracecheck`` lints is byte-for-byte the program a real
    call would run. ``.compile().as_text()`` gives the optimized HLO.
    """
    pm, cmk, hp, hc = _mask_args(P, C, p_mask, c_mask)
    kernels = _kd.resolve(opts.kernel_backend)
    return _solve_impl.lower(P, C, opts, pm, cmk, hp, hc, trace=trace, kernels=kernels)


def solve_jaxpr(P: LinOp, C: LinOp, opts: MWUOptions = MWUOptions(), p_mask=None, c_mask=None, trace=False):
    """The ClosedJaxpr of the solve body (pre-compilation primitive view).

    Traces :func:`_run` directly (under the resolved kernel policy) so
    ``pallas_call`` / collective / callback primitives stay visible —
    the form the jaxpr-level tracecheck rules inspect.
    """
    pm, cmk, hp, hc = _mask_args(P, C, p_mask, c_mask)
    kernels = _kd.resolve(opts.kernel_backend)

    def fn(P, C, pm, cmk):
        return _run(
            P, C, opts,
            pm if hp else None, cmk if hc else None,
            trace=trace, kernels=kernels,
        )

    return jax.make_jaxpr(fn)(P, C, pm, cmk)


def solve_traced(P: LinOp, C: LinOp, opts: MWUOptions = MWUOptions(), p_mask=None, c_mask=None):
    """Tracing solve recording per-iteration diagnostics (Fig. 3).

    Same compiled ``lax.while_loop`` as :func:`solve`, with the
    ``io_callback`` trace hook enabled. Returns (MWUResult, trace) with
    trace = dict of numpy arrays: ``max_violation`` = max(0, max(Px)-1,
    1-min(Cx)) sampled at the start of every iteration (plus the final
    state when the loop exits before the iteration cap), ``alpha``,
    ``probes``.
    """
    pm, cmk, hp, hc = _mask_args(P, C, p_mask, c_mask)
    kernels = _kd.resolve(opts.kernel_backend)
    _TRACE.rows = []
    try:
        res = _solve_impl(P, C, opts, pm, cmk, hp, hc, trace=True, kernels=kernels)
        jax.block_until_ready(res.x)
        jax.effects_barrier()
        rows = sorted(_TRACE.rows)
    finally:
        _TRACE.rows = None

    viol = [r[1] for r in rows]
    alphas = [r[2] for r in rows]
    probes = [r[3] for r in rows]
    if int(res.iters) < opts.max_iter:
        # loop exited through its own condition: record the final state,
        # matching the python-stepped driver this replaced.
        viol.append(max(0.0, float(res.max_px) - 1.0, 1.0 - float(res.min_cx)))
    trace = {
        "max_violation": np.asarray(viol),
        "alpha": np.asarray(alphas),
        "probes": np.asarray(probes),
    }
    return res, trace
