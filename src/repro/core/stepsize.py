"""Step-size search for MWU (paper §4, Algorithms 2-3).

Given the current constraint values y = Px, z = Cx and the step images
d_y = Pd, d_z = Cd, find the largest step size alpha such that the
*bang-for-buck* invariant holds (paper eq. 16):

    f(alpha) = Phi(alpha) / Psi(alpha) >= 1,

    Phi(alpha) = smin_eta(z + alpha d_z) - smin_eta(z)   (covering gain)
    Psi(alpha) = smax_eta(y + alpha d_y) - smax_eta(y)   (packing cost)

f is monotone decreasing in alpha (paper Prop. 4.2), so the maximal
feasible alpha is found by exponential + binary search (Algorithm 3), or
by a warm-started, safeguarded Newton iteration on g(alpha) = f(alpha)-1
with the closed-form derivative

    Psi'(alpha) = < softmax(eta (y + alpha d_y)), d_y >
    Phi'(alpha) = < softmax(-eta (z + alpha d_z)), d_z >.

All searches early-return as soon as min(z + alpha d_z) >= 1 while
f(alpha) >= 1 (Algorithm 3 line 4): that step completes the solve.

Everything here runs inside the jitted MWU while-loop, so the searches
are themselves ``lax.while_loop``s with iteration caps. Probe counts are
returned for the Table-3 statistics.

Probes dominate MWU runtime (Table 3: tens of probes per iteration, each
a multi-pass reduction over both constraint vectors). Under a pallas
:class:`~repro.kernels.dispatch.KernelPolicy`, :func:`make_probe_fn`
therefore routes every probe through the fused
``kernels.linesearch_probe`` sweep — one pass over (y, dy) and one over
(z, dz) yields Psi/Phi, their Newton slopes, and the completion test
``min(z + alpha dz)``, collapsing the ~6 m-length passes the XLA path
below reads per probe. Masked problems (padded lpserve rows) and the
default XLA policy keep the jnp path, which doubles as the kernel's
oracle.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import dispatch as _kd
from .smoothing import logsumexp_shifted

__all__ = ["StepSizeResult", "standard_step", "binary_search_step", "newton_step"]

_MAX_EXP_ITERS = 64  # 2^64 dynamic range is enough for any float32/64 alpha
_MAX_BIN_ITERS = 64
_MAX_NEWTON_ITERS = 30
_MAX_BACKOFF_ITERS = 64


class StepSizeResult(NamedTuple):
    alpha: jax.Array  # chosen step size (>= 1 on feasible instances)
    probes: jax.Array  # number of f(alpha) evaluations (Table 3 "step size iters")
    completes: jax.Array  # bool: this step satisfies all covering constraints


def _masked_min(v, mask):
    if mask is None:
        return jnp.min(v)
    return jnp.min(jnp.where(mask, v, jnp.inf))


class _Probe(NamedTuple):
    """f(alpha) and its pieces at one probe point."""

    f: jax.Array
    phi: jax.Array
    psi: jax.Array
    dphi: jax.Array
    dpsi: jax.Array
    min_z: jax.Array  # min of covering values at this alpha


_EXPM1_MAX = 30.0  # w0 * expm1(t) is taken as exp(log w0 + t) beyond this t


def _lse_increment(diff, log_w0, t):
    """``lse(a + t) - lse(a)``, accurate however small it is.

    ``diff`` is the plain difference of the two logsumexps; ``log_w0`` is
    ``log softmax(a)``, and ``w0 = exp(log_w0)``. A small step's
    increment is tiny next to lse itself, so ``diff`` cancels: it carries
    an absolute error of a few ulp of ``lse(a)`` (4% of Psi on the first
    MWU iteration of rgg-11 matching in f32 on a CPU, and enough on a TPU
    to make every ``f(alpha) < 1``). The increment equals
    ``log1p(S)``, ``S = sum_i w0_i expm1(t_i)``, whose terms share one sign
    (``t`` is ``alpha * eta * dy`` with ``dy >= 0``, or its negation), so
    ``S`` and the increment keep f32's relative precision. While
    ``-1/2 < S < 1`` (|increment| < 0.7) that form is used; beyond, the
    increment is large next to the rounding of ``diff``, which is used.
    Both stay close at the switch, so ``f`` stays continuous for Newton.
    """
    s = jnp.sum(
        jnp.where(
            t <= _EXPM1_MAX,
            jnp.exp(log_w0) * jnp.expm1(jnp.minimum(t, _EXPM1_MAX)),
            jnp.exp(jnp.minimum(log_w0 + t, 80.0)),  # >= 1 wherever it matters
        )
    )
    small = (s > -0.5) & (s < 1.0)
    return jnp.where(small, jnp.log1p(jnp.where(small, s, 0.0)), diff)


def make_probe_fn(y, z, dy, dz, eta, p_mask=None, c_mask=None, with_grad=False):
    """Close over the iteration state; returns probe(alpha) -> _Probe.

    Dispatch (decided once, at trace time): unmasked problems under a
    pallas policy evaluate each probe as two fused ``linesearch_probe``
    kernel sweeps (packing side sign=+1, covering side sign=-1 — lse,
    Newton slope and min(z + alpha dz) in one read of each vector pair);
    otherwise the jnp path below computes the same quantities from
    shared-shift logsumexps.
    """
    tiny = jnp.asarray(jnp.finfo(y.dtype).tiny, y.dtype)

    if p_mask is None and c_mask is None and _kd.choose("probe", y) == "pallas":
        dt = y.dtype
        eta_ = jnp.asarray(eta, dt)
        zero = jnp.zeros((), dt)
        lse_y0, _, _ = _kd.probe_pallas(y, dy, zero, eta_, sign=1.0)
        lse_z0, _, _ = _kd.probe_pallas(z, dz, zero, eta_, sign=-1.0)

        def probe_kernel(alpha):
            lse_ya, dpsi, _ = _kd.probe_pallas(y, dy, alpha, eta_, sign=1.0)
            lse_za, dphi, min_z = _kd.probe_pallas(z, dz, alpha, eta_, sign=-1.0)
            psi = (lse_ya - lse_y0) / eta_
            phi = -(lse_za - lse_z0) / eta_  # smin = -lse(-eta z)/eta
            f = jnp.where(psi <= tiny, jnp.inf, phi / jnp.maximum(psi, tiny))
            # the kernel's Newton slopes are free; with_grad is moot here
            return _Probe(f=f, phi=phi, psi=psi, dphi=dphi, dpsi=dpsi, min_z=min_z)

        return probe_kernel

    ay = eta * y
    az = -eta * z
    ty = eta * dy  # d(ay)/d(alpha)
    tz = -eta * dz
    if p_mask is not None:
        ay = jnp.where(p_mask, ay, -jnp.inf)
        ty = jnp.where(p_mask, ty, 0)
    if c_mask is not None:
        az = jnp.where(c_mask, az, -jnp.inf)
        tz = jnp.where(c_mask, tz, 0)
    lse_y0, _ = logsumexp_shifted(ay)
    lse_z0, _ = logsumexp_shifted(az)
    # log softmax at alpha = 0 (-inf on masked rows, whose weight is 0)
    lwy0 = ay - lse_y0
    lwz0 = az - lse_z0

    def probe(alpha):
        ya = ay + alpha * ty
        za = az + alpha * tz
        lse_ya, sy = logsumexp_shifted(ya)
        lse_za, sz = logsumexp_shifted(za)
        # Psi = smax(y+a dy) - smax(y);  Phi = smin(z+a dz) - smin(z)
        psi = _lse_increment(lse_ya - lse_y0, lwy0, alpha * ty) / eta
        phi = -_lse_increment(lse_za - lse_z0, lwz0, alpha * tz) / eta  # smin = -lse(-eta z)/eta
        # covering must improve and packing must not decrease for the
        # invariant to be meaningful; on degenerate steps psi can be ~0.
        f = jnp.where(psi <= tiny, jnp.inf, phi / jnp.maximum(psi, tiny))
        if with_grad:
            wy = jnp.exp(ya - lse_ya)  # softmax(eta(y+a dy))
            wz = jnp.exp(za - lse_za)  # softmax(-eta(z+a dz))
            dpsi = jnp.dot(wy, dy)
            dphi = jnp.dot(wz, dz)
        else:
            dpsi = jnp.zeros((), y.dtype)
            dphi = jnp.zeros((), y.dtype)
        min_z = _masked_min(z + alpha * dz, c_mask)
        return _Probe(f=f, phi=phi, psi=psi, dphi=dphi, dpsi=dpsi, min_z=min_z)

    return probe


def standard_step(y, z, dy, dz, eta, p_mask=None, c_mask=None, ls_eps=0.1, alpha0=None):
    """The theoretical step alpha = 1 (Mahoney et al. implicit choice)."""
    one = jnp.ones((), y.dtype)
    min_z = _masked_min(z + dz, c_mask)
    return StepSizeResult(alpha=one, probes=jnp.zeros((), jnp.int32), completes=min_z >= 1)


def _refine_completion(probe, hi, ls_eps):
    """Smallest alpha in (0, hi] with min_z(alpha) >= 1 (monotone in alpha).

    The completing step must not overshoot: the potential argument only
    bounds smax(Px) by f0 + smin(Cx), so covering overshoot translates
    directly into packing violation beyond (1+eps). Bisect to within
    ls_eps relative width; the result still satisfies the bang-for-buck
    invariant because f is decreasing (smaller alpha => larger f).
    """

    def cond(s):
        lo, h, n = s
        return (h - lo > ls_eps * h) & (n < _MAX_BIN_ITERS)

    def body(s):
        lo, h, n = s
        mid = 0.5 * (lo + h)
        ok = probe(mid).min_z >= 1
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, h), n + 1

    lo, h, n = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(hi), hi, jnp.zeros((), jnp.int32))
    )
    return jnp.maximum(h, jnp.ones_like(h)), n


def binary_search_step(y, z, dy, dz, eta, p_mask=None, c_mask=None, ls_eps=0.1, alpha0=None):
    """Algorithm 3: exponential bracket + binary search, warm-startable.

    Returns the largest alpha with f(alpha) >= 1 up to relative width
    ls_eps. If that alpha is < 1 the caller must declare infeasibility
    (paper, Alg. 2 line 12).
    """
    probe = make_probe_fn(y, z, dy, dz, eta, p_mask, c_mask)
    dt = y.dtype
    a0 = jnp.ones((), dt) if alpha0 is None else jnp.maximum(alpha0.astype(dt), 1.0)

    p0 = probe(a0)
    n0 = jnp.ones((), jnp.int32)

    # --- upward exponential phase: double while f >= 1 ------------------
    def up_cond(s):
        a, p, n = s
        # stop on bracket (f < 1) or on covering completion (Alg. 3 line 4)
        return (p.f >= 1) & (p.min_z < 1) & (n < _MAX_EXP_ITERS)

    def up_body(s):
        a, p, n = s
        a2 = a * 2
        return a2, probe(a2), n + 1

    a_up, p_up, n_up = jax.lax.while_loop(up_cond, up_body, (a0, p0, n0))
    completed_up = (p_up.f >= 1) & (p_up.min_z >= 1)

    # --- downward exponential phase (warm start overshot): halve while f < 1
    def dn_cond(s):
        a, p, n = s
        return (p.f < 1) & (a > 1e-12) & (n < _MAX_EXP_ITERS)

    def dn_body(s):
        a, p, n = s
        a2 = a / 2
        return a2, probe(a2), n + 1

    need_down = p0.f < 1
    a_dn, p_dn, n_dn = jax.lax.while_loop(
        dn_cond, dn_body, (a0, p0, jnp.zeros((), jnp.int32))
    )

    # bracket [lb, ub] with f(lb) >= 1 > f(ub)
    lb = jnp.where(need_down, a_dn, a_up / 2)
    ub = jnp.where(need_down, a_dn * 2, a_up)
    n_exp = jnp.where(need_down, n0 + n_dn, n_up)

    # --- binary phase ----------------------------------------------------
    def bin_cond(s):
        lb, ub, n, done = s
        return (~done) & (ub - lb > ls_eps * lb) & (n < _MAX_BIN_ITERS)

    def bin_body(s):
        lb, ub, n, done = s
        beta = 0.5 * (lb + ub)
        p = probe(beta)
        ok = p.f >= 1
        done = ok & (p.min_z >= 1)
        lb = jnp.where(ok, beta, lb)
        ub = jnp.where(ok, ub, beta)
        return lb, ub, n + 1, done

    lb, ub, n_bin, _ = jax.lax.while_loop(
        bin_cond, bin_body, (lb, ub, jnp.zeros((), jnp.int32), completed_up)
    )

    alpha = jnp.where(completed_up, a_up, lb)
    # If this step completes the covering constraints, shrink it to the
    # *smallest* completing alpha so packing does not overshoot (1+eps).
    completes = _masked_min(z + alpha * dz, c_mask) >= 1

    def do_refine():
        return _refine_completion(probe, alpha, ls_eps)

    alpha, n_ref = jax.lax.cond(
        completes, do_refine, lambda: (alpha, jnp.zeros((), jnp.int32))
    )
    return StepSizeResult(alpha=alpha, probes=n_exp + n_bin + n_ref, completes=completes)


def newton_step(y, z, dy, dz, eta, p_mask=None, c_mask=None, ls_eps=0.1, alpha0=None):
    """Warm-started, safeguarded Newton on g(alpha) = f(alpha) - 1 (§4.2).

    After convergence, multiplicatively backs off by (1 - ls_eps) until the
    bang-for-buck invariant (16) holds, as the paper prescribes.
    """
    probe = make_probe_fn(y, z, dy, dz, eta, p_mask, c_mask, with_grad=True)
    dt = y.dtype
    a0 = jnp.ones((), dt) if alpha0 is None else jnp.maximum(alpha0.astype(dt), 1e-6)

    def nt_cond(s):
        a, p, n, done = s
        return (~done) & (n < _MAX_NEWTON_ITERS)

    def nt_body(s):
        a, p, n, done = s
        # f' = (Phi' Psi - Phi Psi') / Psi^2   (negative: f is decreasing)
        tiny = jnp.asarray(jnp.finfo(dt).tiny, dt)
        psi2 = jnp.maximum(p.psi * p.psi, tiny)
        fp = (p.dphi * p.psi - p.phi * p.dpsi) / psi2
        fp = jnp.minimum(fp, -tiny)  # enforce the known sign
        raw = a - (p.f - 1.0) / fp
        # trust-region safeguard: at most 8x move per iteration
        a2 = jnp.clip(raw, a * 0.125, a * 8.0)
        a2 = jnp.maximum(a2, 1e-12)
        p2 = probe(a2)
        done = (jnp.abs(a2 - a) <= ls_eps * a) | ((p2.f >= 1) & (p2.min_z >= 1))
        return a2, p2, n + 1, done

    p0 = probe(a0)
    a, p, n, _ = jax.lax.while_loop(nt_cond, nt_body, (a0, p0, jnp.ones((), jnp.int32), jnp.zeros((), bool)))

    # back off multiplicatively until invariant satisfied (paper §4.2)
    def bo_cond(s):
        a, p, n = s
        return (p.f < 1) & (n < _MAX_BACKOFF_ITERS)

    def bo_body(s):
        a, p, n = s
        a2 = a * (1.0 - ls_eps)
        return a2, probe(a2), n + 1

    a, p, n_bo = jax.lax.while_loop(bo_cond, bo_body, (a, p, jnp.zeros((), jnp.int32)))

    # completion refinement: smallest alpha that satisfies covering
    completes = (p.min_z >= 1) & (p.f >= 1)

    def do_refine():
        return _refine_completion(probe, a, ls_eps)

    a, n_ref = jax.lax.cond(completes, do_refine, lambda: (a, jnp.zeros((), jnp.int32)))
    return StepSizeResult(alpha=a, probes=n + n_bo + n_ref, completes=completes)


STEP_RULES = {
    "std": standard_step,
    "binary": binary_search_step,
    "newton": newton_step,
}
