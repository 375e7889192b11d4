"""Pallas TPU kernel: fused constraint update + termination reductions.

    out = y + alpha * dy      and simultaneously  (min(out), max(out))

One HBM sweep covers Alg. 2 lines 14-15 plus the loop-condition
reductions (max packing / min covering values) that would otherwise be
three extra passes — the same fusion the paper implements with OpenMP
loop fusion (§5.1.3). ``core.mwu._iteration`` routes the x/y/z update
triple through this kernel when the dispatch layer selects pallas.
Padded lanes contribute +inf/-inf neutrally; arithmetic runs in the
input dtype.
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


def _axpy_kernel(n, alpha_ref, y_ref, dy_ref, out_ref, red_ref, mn_ref, mx_ref):
    """out tile = y + alpha*dy; red rows 0/1 = [min, max] after the last tile.

    The running min/max are per-lane (8, 128) vectors folded on the last
    grid step (Mosaic cannot store a scalar to VMEM).
    """
    i = pl.program_id(0)
    dt = mn_ref.dtype

    @pl.when(i == 0)
    def _init():
        mn_ref[...] = jnp.full((SUBLANES, LANES), _POS, dt)  # running min
        mx_ref[...] = jnp.full((SUBLANES, LANES), _NEG, dt)  # running max

    out = y_ref[...] + alpha_ref[0] * dy_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES + jax.lax.broadcasted_iota(
        jnp.int32, (SUBLANES, LANES), 1
    )
    valid = (i * TILE + idx) < n
    out_ref[...] = jnp.where(valid, out, jnp.zeros((), dt))
    mn_ref[...] = jnp.minimum(mn_ref[...], jnp.where(valid, out, jnp.asarray(_POS, dt)))
    mx_ref[...] = jnp.maximum(mx_ref[...], jnp.where(valid, out, jnp.asarray(_NEG, dt)))

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
        red_ref[...] = jnp.where(row == 0, jnp.min(mn_ref[...]), jnp.max(mx_ref[...])).astype(dt)


def axpy_reduce_pallas(y, dy, alpha, interpret: bool = True):
    """Returns (y + alpha*dy, min, max) in one pass."""
    n = y.shape[0]
    dt = y.dtype
    nt = max(1, (n + TILE - 1) // TILE)
    pad = nt * TILE - n
    yp = jnp.pad(y, (0, pad)).reshape(nt * SUBLANES, LANES)
    dp = jnp.pad(dy.astype(dt), (0, pad)).reshape(nt * SUBLANES, LANES)
    a = alpha.astype(dt).reshape(1)
    tile = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
    out, red = pl.pallas_call(
        functools.partial(_axpy_kernel, n),
        grid=(nt,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile],
        out_specs=[tile, pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((nt * SUBLANES, LANES), dt),
            jax.ShapeDtypeStruct((SUBLANES, LANES), dt),
        ],
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), dt)] * 2,
        interpret=interpret,
    )(a, yp, dp)
    return out.reshape(-1)[:n], red[0, 0], red[1, 0]
