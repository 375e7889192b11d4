"""Pallas TPU kernel: fused eta-softmax weights + smoothed max (paper §5.1.3).

Computes, in two HBM sweeps over a length-n vector v:

    lse  = logsumexp(sign * eta * v)         (pass 1: online max/sum)
    w    = exp(sign * eta * v - lse)         (pass 2: normalized weights)

which yields both smax_eta/smin_eta (= sign * lse / eta) and the MWU
weight vector grad smax/smin in one fused pipeline — the paper fuses
exactly this gradient computation on CPU with OpenMP + AVX-512; on TPU
the tile is an (8, 128)-aligned VMEM block and the reduction carry is a
per-lane (8, 128) VMEM scratch across a sequential 1-D grid. Scalars
(sign*eta, lse) enter through SMEM; the lse result leaves as a
broadcast (8, 128) block, since Mosaic cannot store a scalar to VMEM.

All arithmetic runs in the input dtype (f32 or, in interpret mode, f64 —
the dispatch gate keeps f64 off real TPUs), so kernel and XLA paths
agree to summation-order differences only.

Masked (padded) entries are handled by an explicit length argument:
lanes with global index >= n contribute -inf / 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES  # 1024 elements per VMEM tile

_NEG = -1e30


def _valid(i, n):
    """Mask of the tile's lanes whose global index is below ``n``."""
    idx = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES + jax.lax.broadcasted_iota(
        jnp.int32, (SUBLANES, LANES), 1
    )
    return (i * TILE + idx) < n


def _reduce_kernel(n, se_ref, v_ref, out_ref, m_ref, s_ref):
    """Pass 1: per-lane running (max m, sum s); writes lse at the end.

    The carries are (8, 128) vectors, one online logsumexp per lane, so
    no scalar ever lives in VMEM; the last step folds the lanes and
    broadcasts lse over the (8, 128) out block.
    """
    i = pl.program_id(0)
    dt = m_ref.dtype

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full((SUBLANES, LANES), _NEG, dt)
        s_ref[...] = jnp.zeros((SUBLANES, LANES), dt)

    valid = _valid(i, n)
    a = jnp.where(valid, v_ref[...] * se_ref[0], jnp.asarray(_NEG, dt))
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, a)
    e = jnp.where(valid, jnp.exp(a - m_new), jnp.zeros((), dt))
    s_ref[...] = s_ref[...] * jnp.exp(m_old - m_new) + e
    m_ref[...] = m_new

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        m = m_ref[...]
        mx = jnp.max(m)
        lse = mx + jnp.log(jnp.sum(s_ref[...] * jnp.exp(m - mx)))
        out_ref[...] = jnp.full((SUBLANES, LANES), lse, dt)


def _normalize_kernel(n, se_ref, v_ref, lse_ref, w_ref):
    """Pass 2: w = exp(sign*eta*v - lse), zero on padded lanes."""
    a = v_ref[...] * se_ref[0]
    w = jnp.exp(a - lse_ref[0])
    w_ref[...] = jnp.where(_valid(pl.program_id(0), n), w, jnp.zeros((), w.dtype)).astype(w_ref.dtype)


def softmax_weights_pallas(v, eta, sign: float = 1.0, interpret: bool = True):
    """Returns (lse, w) with lse = logsumexp(sign*eta*v), w = softmax(sign*eta*v)."""
    n = v.shape[0]
    dt = v.dtype
    nt = max(1, (n + TILE - 1) // TILE)
    vp = jnp.pad(v, (0, nt * TILE - n)).reshape(nt * SUBLANES, LANES)
    se = (jnp.asarray(sign, dt) * eta.astype(dt)).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))

    stats = pl.pallas_call(
        functools.partial(_reduce_kernel, n),
        grid=(nt,),
        in_specs=[smem, tile],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), dt),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), dt)] * 2,
        interpret=interpret,
    )(se, vp)
    lse = stats[0, 0]

    w = pl.pallas_call(
        functools.partial(_normalize_kernel, n),
        grid=(nt,),
        in_specs=[smem, tile, smem],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nt * SUBLANES, LANES), dt),
        interpret=interpret,
    )(se, vp, lse.reshape(1))
    return lse, w.reshape(-1)[:n]
