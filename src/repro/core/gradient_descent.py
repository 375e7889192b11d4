"""MPCSolver baseline: stateless gradient descent with adaptive error.

Reimplementation of the comparison algorithm of Makari et al. [31]
(based on Awerbuch & Khandekar's stateless distributed gradient descent
[7]), as described in the paper's Appendix A.3, for the Figure-3
convergence study. It minimizes

    Gamma(x) = sum_i exp(mu (P_i x - 1)) + sum_i exp(mu (1 - C_i x))

by multiplicative coordinate updates: coordinates whose covering pull
exceeds their packing pull (C^T z vs P^T y) are scaled up, the opposite
scaled down. The *adaptive error* strategy starts with a coarse internal
tolerance eps' >> eps (mu ~ log(m)/eps' small => big moves) and tightens
eps' whenever progress stagnates, warm-starting from the current x.

Exact constants in [31] are tuned per-problem; we follow the published
structure (mu = ln(3m/eps')/eps', multiplicative step beta = eps'/8,
stagnation window + halving) and note this is a faithful *shape*
reproduction used for iteration-count comparison, as the paper itself
compares iteration counts, not wall time, against this method.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .operators import LinOp, with_endpoint_order

__all__ = ["MPCOptions", "mpc_solve"]


@dataclass(frozen=True)
class MPCOptions:
    eps: float = 0.05  # target relative error (Makari et al. use 0.05)
    eps_internal0: float = 1.0  # initial adaptive internal error
    max_iter: int = 20000
    stagnation_window: int = 50
    stagnation_rtol: float = 1e-3
    beta_factor: float = 0.125  # beta = beta_factor * eps'


@partial(jax.jit, static_argnames=("has_mask",))
def _mpc_iter(P: LinOp, C: LinOp, x, mu, beta, x_max, c_mask, has_mask):
    y = jnp.exp(jnp.clip(mu * (P.matvec(x) - 1.0), -60.0, 60.0))
    zc = C.matvec(x)
    z = jnp.exp(jnp.clip(mu * (1.0 - zc), -60.0, 60.0))
    if has_mask:
        z = jnp.where(c_mask, z, 0.0)
    gp = P.rmatvec(y)  # packing push (wants x smaller)
    gc = C.rmatvec(z)  # covering pull (wants x larger)
    up = gc > (1.0 + beta) * gp
    dn = gp > (1.0 + beta) * gc
    fac = jnp.where(up, 1.0 + beta, jnp.where(dn, 1.0 - beta, 1.0))
    x2 = jnp.clip(x * fac, 1e-30, x_max)
    z2 = C.matvec(x2)
    min_c = jnp.min(jnp.where(c_mask, z2, jnp.inf)) if has_mask else jnp.min(z2)
    viol = jnp.maximum(
        0.0, jnp.maximum(jnp.max(P.matvec(x2)) - 1.0, 1.0 - min_c)
    )
    return x2, viol


def mpc_solve(P: LinOp, C: LinOp, opts: MPCOptions = MPCOptions(), c_mask=None):
    """Run MPCSolver; returns (x, trace dict) with per-iteration violation."""
    P, C = with_endpoint_order(P), with_endpoint_order(C)  # sorted once, not per iteration
    m = P.shape[0] + C.shape[0]
    n = P.shape[1]
    dt = jnp.result_type(float)  # canonical float: f64 iff x64 is enabled

    # start tiny like MWU so packing starts satisfied
    cm = P.colmax().astype(dt)
    safe = jnp.where(cm > 0, cm, 1.0)
    x = (opts.eps / (n * safe)).astype(dt)
    x_max = jnp.asarray(float(n), dt)  # generous cap

    has_mask = c_mask is not None
    cm = c_mask if has_mask else jnp.zeros((C.shape[0],), bool)
    eps_i = opts.eps_internal0
    viols = []
    it = 0
    best_recent = np.inf
    window_count = 0
    while it < opts.max_iter:
        mu = jnp.asarray(np.log(3 * m / opts.eps) / eps_i, dt)
        beta = jnp.asarray(opts.beta_factor * eps_i, dt)
        x, viol = _mpc_iter(P, C, x, mu, beta, x_max, cm, has_mask)
        v = float(viol)
        viols.append(v)
        it += 1
        if v <= opts.eps:
            break
        # adaptive error: tighten eps' when stagnating (Appendix A.3)
        if v < best_recent * (1.0 - opts.stagnation_rtol):
            best_recent = v
            window_count = 0
        else:
            window_count += 1
            if window_count >= opts.stagnation_window:
                eps_i = max(eps_i / 2.0, opts.eps)
                best_recent = np.inf
                window_count = 0
    return np.asarray(x), {"max_violation": np.asarray(viols), "iters": it}
