"""Sharding layout for ``Problem`` pytrees on a (pod, data) mesh.

PAPER.md's MPI scheme partitions the incidence matrix by edges: each
rank owns an edge slab, runs the gather/scatter kernels on its slab,
and exchanges the vertex-space coupling terms (the smax/smin gradient
weights live in vertex space) with its neighbors. This module is the
SPMD translation of that layout:

* **edge_slab mode** — the paper's scheme, verbatim. For packing
  problems whose operator is an :class:`~repro.core.operators.Incidence`
  with an objective-covering row (matching / b-matching — the paper's
  flagship distributed workload), the edge-dimension leaves
  (``P.u``, ``P.v``, ``P.weights``, ``P.edge_mask``, ``c``) shard
  across ``pod`` via :func:`repro.sparsela.partition.partition_edges_1d`.
  Each device runs the fused Pallas kernel pack on its local edge slab;
  the per-iteration vertex images ``y = Px`` / ``dy = Pd`` and the
  objective row ``z = <c,x>/M`` are completed by one ``psum`` each
  (:class:`PodSum`) — the psum plays the role of the paper's neighbor
  exchange, and constraint-space vectors stay replicated so the
  smoothing / line-search math is untouched.

* **column mode** — the generic fallback for every other family
  (vertex cover, dominating set, densest subgraph, generalized
  matching). The operator leaves stay replicated; :class:`SlabCols`
  views a contiguous *column* (variable) slab as the local operator by
  embedding the slab into the full column space for ``matvec`` (then
  psum) and extracting the slab from full-width ``rmatvec``/``colmax``
  results. Correct SPMD semantics on any operator zoo member — but no
  per-device work reduction; it exists so ``DistSolver`` is total over
  the Problem surface, and so the ``data``-axis fan-out (which IS a
  real speedup for every family) composes with a nontrivial pod axis.

Replication invariant (what makes the core driver reusable): every
constraint-row vector (y, z, dy, dz, the masks, every line-search
probe) is replicated across ``pod`` because the wrapped ``matvec``
psums; only two *variable-space* reductions in the whole MWU loop need
axis-awareness (``init_x``'s fallback min, the infeasible-direction
``max(d)``), which ``core.mwu._run`` handles via its ``axis`` argument.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from ..api.problem import Problem
from ..core.operators import Incidence, LinOp, register_op, static_field
from ..sparsela.partition import partition_edges_1d
from .mesh import DATA_AXIS, POD_AXIS

__all__ = [
    "PodSum",
    "SlabCols",
    "pod_mode",
    "slab_pad_problem",
    "problem_specs",
    "bounds_spec",
    "result_specs",
    "global_columns",
]


# ------------------------------------------------------------- operators --
@register_op
@dataclass
class PodSum(LinOp):
    """Edge-slab wrapper: local scatter, psum-completed constraint rows.

    ``inner`` is built from this device's edge slab but keeps *global*
    vertex ids (rows). ``matvec`` therefore produces a partial
    constraint image which one ``psum`` over ``axis`` completes — after
    which y/z are fully replicated, so ``rmatvec`` (gather of a
    replicated vector onto the local slab) and ``colmax`` (per-local-
    column) need no communication at all. This is the paper's
    edge-partitioned SpMV pair with psum as the exchange.
    """

    inner: LinOp
    axis: str = static_field(default=POD_AXIS)

    @property
    def shape(self):
        return self.inner.shape

    def matvec(self, x):
        return lax.psum(self.inner.matvec(x), self.axis)

    def rmatvec(self, y):
        return self.inner.rmatvec(y)

    def colmax(self, row_scale=None):
        return self.inner.colmax(row_scale)

    @property
    def nnz(self):
        return self.inner.nnz


@register_op
@dataclass
class SlabCols(LinOp):
    """Column-slab view of a replicated operator (generic pod fallback).

    Device k owns columns ``[k * block, (k + 1) * block)`` of the
    ``n_cols``-wide ``inner`` (whose leaves are replicated across the
    axis). ``matvec`` embeds the local slab into a zero-padded full
    vector, applies ``inner`` and psums the linear partials;
    ``rmatvec``/``colmax`` compute full-width and extract the slab.
    Semantically exact for any linear operator; the per-device matvec
    work is NOT reduced (see module docstring for why it exists).
    """

    inner: LinOp
    block: int = static_field(default=0)  # local slab width
    n_pod: int = static_field(default=1)  # devices on the axis
    n_cols: int = static_field(default=0)  # true global column count
    axis: str = static_field(default=POD_AXIS)

    @property
    def shape(self):
        return (self.inner.shape[0], self.block)

    def _embed(self, x):
        """Local slab -> full (n_cols,) vector, zeros elsewhere."""
        buf = jnp.zeros((self.block * self.n_pod,), x.dtype)
        start = lax.axis_index(self.axis) * self.block
        buf = lax.dynamic_update_slice(buf, x, (start,))
        return buf[: self.n_cols]

    def _extract(self, full):
        """Full (n_cols,) vector -> this device's slab (zero past the end)."""
        pad = self.block * self.n_pod - self.n_cols
        fullp = jnp.pad(full, (0, pad))
        start = lax.axis_index(self.axis) * self.block
        return lax.dynamic_slice(fullp, (start,), (self.block,))

    def matvec(self, x):
        return lax.psum(self.inner.matvec(self._embed(x)), self.axis)

    def rmatvec(self, y):
        return self._extract(self.inner.rmatvec(y))

    def colmax(self, row_scale=None):
        return self._extract(self.inner.colmax(row_scale))

    @property
    def nnz(self):
        return self.inner.nnz


# ----------------------------------------------------------- mode choice --
def pod_mode(problem: Problem) -> str:
    """``"edge_slab"`` when the paper's edge partition applies, else ``"column"``.

    Edge-slab needs the variables to BE the edges of an ``Incidence``
    packing operator with the objective entering as a covering row
    (``bound_mode="objective_covering"``): then ``P.u/v/weights/
    edge_mask`` and ``c`` are all edge-aligned and slab-shardable.
    """
    P = problem.P
    if (
        problem.bound_mode == "objective_covering"
        and isinstance(P, Incidence)
        and problem.c is not None
        and int(jnp.shape(problem.c)[-1]) == int(jnp.shape(P.u)[-1])
    ):
        return "edge_slab"
    return "column"


# ---------------------------------------------------------- slab padding --
def _pad_last(a, pad: int, fill):
    if a is None or pad == 0:
        return a
    a = jnp.asarray(a)
    widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return jnp.pad(a, widths, constant_values=fill)


def slab_pad_problem(problem: Problem, pod: int) -> tuple[Problem, int]:
    """Pad the edge dimension to a multiple of ``pod`` (edge_slab mode).

    Padded edges are fully masked (``edge_mask=False``, zero objective),
    appended at the global end so contiguous pod slabs reassemble into
    padded-global order and ``x[..., :n_edges]`` strips them. Returns
    ``(padded problem, original edge count)``; with ``pod == 1`` the
    problem is returned untouched (bit-parity with the vmap path).
    """
    P = problem.P
    n_edges = int(jnp.shape(P.u)[-1])
    padded, _ = partition_edges_1d(n_edges, pod)
    pad = padded - n_edges
    if pad == 0:
        return problem, n_edges
    mask = P.edge_mask
    if mask is None:
        mask = jnp.ones(jnp.shape(P.u), bool)
    P2 = Incidence(
        u=_pad_last(P.u, pad, 0),
        v=_pad_last(P.v, pad, 0),
        n_vertices=P.n_vertices,
        weights=_pad_last(P.weights, pad, 0),
        edge_mask=_pad_last(mask, pad, False),
    )
    c2 = _pad_last(problem.c, pad, 0)
    return dataclasses.replace(problem, P=P2, c=c2), n_edges


# -------------------------------------------------------------- specs ----
# Leaf paths (attribute-name tuples) that carry the edge dimension in
# edge_slab mode; everything else is replicated across pod.
_EDGE_LEAF_PATHS = {
    ("P", "u"),
    ("P", "v"),
    ("P", "weights"),
    ("P", "edge_mask"),
    ("c",),
}


def problem_specs(problem: Problem, mode: str, batched: bool):
    """PartitionSpec pytree for a ``Problem`` under the (pod, data) mesh.

    Batched problems (``stack_problems`` output) shard their leading
    instance axis over ``data``; in edge_slab mode the trailing edge
    axis of the edge-aligned leaves additionally shards over ``pod``.
    Every other leaf is replicated (constraint-space masks, bounds,
    column-mode operators). The result feeds ``shard_map`` in_specs and,
    via :func:`repro.launch.mesh.sharding_for`, the ``device_put`` with
    which ``DistSolver`` places each problem on the mesh.
    """
    lead = (DATA_AXIS,) if batched else ()

    def one(path, leaf):
        names = tuple(k.name for k in path if isinstance(k, jax.tree_util.GetAttrKey))
        if mode == "edge_slab" and names in _EDGE_LEAF_PATHS:
            return PartitionSpec(*lead, POD_AXIS)
        return PartitionSpec(*lead)

    return jax.tree_util.tree_map_with_path(one, problem)


def bounds_spec() -> PartitionSpec:
    """Bounds fan out over the data axis (one lane group per data row)."""
    return PartitionSpec(DATA_AXIS)


def result_specs():
    """out_specs for a batched ``MWUResult``: x carries the pod slabs."""
    from ..core.mwu import MWUResult

    return MWUResult(
        x=PartitionSpec(DATA_AXIS, POD_AXIS),
        status=PartitionSpec(DATA_AXIS),
        iters=PartitionSpec(DATA_AXIS),
        ls_probes=PartitionSpec(DATA_AXIS),
        max_px=PartitionSpec(DATA_AXIS),
        min_cx=PartitionSpec(DATA_AXIS),
    )


# ---------------------------------------------------------- column count --
def global_columns(problem: Problem, bound, batched: bool) -> int:
    """Host-side global variable count of the instantiated feasibility LP.

    This is the ``n`` the single-device ``init_x`` would see — the
    distributed driver passes it through ``_run(init_cols=...)`` so the
    init scale (and hence the whole trajectory) matches the unsharded
    solve regardless of slab padding.
    """
    template = problem
    if batched:
        template = jax.tree.map(lambda a: jnp.asarray(a)[0], problem)
    P0, C0, _, _ = template.instantiate(None if problem.bound_mode == "none" else float(bound))
    ref = P0 if P0 is not None else C0
    return int(ref.shape[1])
