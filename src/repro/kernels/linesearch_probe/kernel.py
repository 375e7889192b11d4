"""Pallas TPU kernel: fused line-search probe (paper Alg. 3 inner loop).

For one probe point alpha, over a constraint vector pair (y, dy):

    a_i  = sign * eta * (y_i + alpha * dy_i)
    lse  = logsumexp(a)                      -> Psi/Phi pieces
    t    = sum softmax(a)_i * dy_i           -> Psi'/Phi' (Newton slope)
    mn   = min(y_i + alpha * dy_i)           -> completion test

Everything a binary-search or Newton probe needs, in ONE sweep of
(y, dy) — the unfused XLA path reads both vectors 3-4 times. The paper
identifies exactly this "search" vector work as 20-50% of runtime
(Fig. 5a); this kernel is its TPU counterpart, and
``core.stepsize.make_probe_fn`` routes every probe through it when the
dispatch layer selects the pallas backend.

Online update per lane (flash-style, an (8, 128) vector carry):
    m' = max(m, a);  c = exp(m - m')
    s' = s*c + exp(a - m');  t' = t*c + exp(a - m') * dy
and the last grid step folds the lanes into lse and t/s = <softmax(a), dy>.

Arithmetic runs in the input dtype (f64 stays f64 in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES

_POS = 1e30
_NEG = -1e30


def _probe_kernel(n, scal_ref, y_ref, dy_ref, out_ref, m_ref, s_ref, t_ref, mn_ref):
    """scal = [sign*eta, alpha]; out rows 0/1/2 = [lse, <softmax, dy>, min_v].

    The carries are per-lane (8, 128) vectors; the last grid step folds
    the lanes and writes each result broadcast over one sublane row of
    the (8, 128) out block (Mosaic cannot store a scalar to VMEM).
    """
    i = pl.program_id(0)
    dt = m_ref.dtype

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full((SUBLANES, LANES), _NEG, dt)  # running max m
        s_ref[...] = jnp.zeros((SUBLANES, LANES), dt)  # running s
        t_ref[...] = jnp.zeros((SUBLANES, LANES), dt)  # running t (softmax-weighted dy)
        mn_ref[...] = jnp.full((SUBLANES, LANES), _POS, dt)  # running min of v

    se = scal_ref[0]
    alpha = scal_ref[1]
    dy = dy_ref[...]
    v = y_ref[...] + alpha * dy
    idx = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES + jax.lax.broadcasted_iota(
        jnp.int32, (SUBLANES, LANES), 1
    )
    valid = (i * TILE + idx) < n
    a = jnp.where(valid, v * se, jnp.asarray(_NEG, dt))

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, a)
    c = jnp.exp(m_old - m_new)
    e = jnp.where(valid, jnp.exp(a - m_new), jnp.zeros((), dt))
    m_ref[...] = m_new
    s_ref[...] = s_ref[...] * c + e
    t_ref[...] = t_ref[...] * c + e * dy
    mn_ref[...] = jnp.minimum(mn_ref[...], jnp.where(valid, v, jnp.asarray(_POS, dt)))

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        m = m_ref[...]
        mx = jnp.max(m)
        c = jnp.exp(m - mx)
        s = jnp.sum(s_ref[...] * c)
        lse = mx + jnp.log(s)
        slope = jnp.sum(t_ref[...] * c) / s
        mn = jnp.min(mn_ref[...])
        row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
        out_ref[...] = jnp.where(row == 0, lse, jnp.where(row == 1, slope, mn)).astype(dt)


def linesearch_probe_pallas(y, dy, alpha, eta, sign: float = 1.0, interpret: bool = True):
    """Returns (lse, slope, min_v) for a = sign*eta*(y + alpha*dy)."""
    n = y.shape[0]
    dt = y.dtype
    nt = max(1, (n + TILE - 1) // TILE)
    pad = nt * TILE - n
    yp = jnp.pad(y, (0, pad)).reshape(nt * SUBLANES, LANES)
    dp = jnp.pad(dy.astype(dt), (0, pad)).reshape(nt * SUBLANES, LANES)
    scal = jnp.stack([jnp.asarray(sign, dt) * eta.astype(dt), alpha.astype(dt)])
    tile = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_probe_kernel, n),
        grid=(nt,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), dt),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), dt)] * 4,
        interpret=interpret,
    )(scal, yp, dp)
    return out[0, 0], out[1, 0], out[2, 0]
