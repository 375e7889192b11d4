"""Pallas TPU kernel: implicit incidence transpose-product (paper §5.1.2).

    g_e = w[u_e] + w[v_e]            (= (M^T w)_e, optionally * edge weight)

This is the gather-direction SpMV the paper credits with its largest
implicit-representation speedups (5.06x on bmatch): the incidence matrix
is never materialized — the edge list *is* the operator. On TPU, edge
index tiles stream through VMEM while the vertex vector w is resident
(blocked by vertex range for large graphs; the grid's second axis walks
vertex blocks, accumulating partial gathers — edges are pre-sorted by
endpoint block by `sparsela.partition`, so each edge tile touches one
block per endpoint).

This single-block variant holds w fully in VMEM and indexes it with
``jnp.take`` on a 1-D ref. Mosaic cannot lower that ("Only 2D gather is
supported"), so the kernel runs only in interpret mode: the dispatch
gate sends every gather under a non-interpret policy to XLA and counts
the reason (``dispatch.GATHER_NO_TPU_LOWERING``) in ``dispatch.stats()``.
In interpret mode the gate also falls back beyond
`repro.kernels.dispatch.VMEM_VERTEX_LIMIT` (3M f32 vertices — see the
headroom math there). The gather
runs in the input dtype end to end: f64 solves keep full precision
through the kernel path (interpret mode; real TPUs gate f64 to XLA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES


def _gather_kernel(E, u_ref, v_ref, w_ref, out_ref):
    i = pl.program_id(0)
    u = u_ref[...]
    v = v_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES + jax.lax.broadcasted_iota(
        jnp.int32, (SUBLANES, LANES), 1
    )
    valid = (i * TILE + idx) < E
    u = jnp.where(valid, u, 0)
    v = jnp.where(valid, v, 0)
    w = w_ref[...]
    g = jnp.take(w, u.reshape(-1), axis=0) + jnp.take(w, v.reshape(-1), axis=0)
    out_ref[...] = jnp.where(valid, g.reshape(SUBLANES, LANES), jnp.zeros((), w.dtype))


def incidence_gather_pallas(u, v, w, interpret: bool = True):
    """g[e] = w[u[e]] + w[v[e]]; zero for padded edge slots."""
    E = u.shape[0]
    nt = max(1, (E + TILE - 1) // TILE)
    pad = nt * TILE - E
    up = jnp.pad(u, (0, pad)).reshape(nt * SUBLANES, LANES)
    vp = jnp.pad(v, (0, pad)).reshape(nt * SUBLANES, LANES)
    n = w.shape[0]
    n_pad = ((n + LANES - 1) // LANES) * LANES
    wp = jnp.pad(w, (0, n_pad - n))

    g = pl.pallas_call(
        functools.partial(_gather_kernel, E),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            pl.BlockSpec((n_pad,), lambda i: (0,)),  # w resident in VMEM
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nt * SUBLANES, LANES), w.dtype),
        interpret=interpret,
    )(up, vp, wp)
    return g.reshape(-1)[:E]
