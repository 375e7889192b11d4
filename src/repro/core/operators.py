"""Linear operators for positive LPs (paper §3 + §5.1.2).

The paper's key software contribution is *implicit* representations of the
constraint matrices that arise in graph LPs:

* ``Incidence``        M  (|V| x |E|)  — matching / bmatch packing rows,
                                          transposed for vertex-cover.
* ``AdjacencyPlusId``  I+A (|V| x |V|) — dominating-set covering rows.
* ``VertexEdgePair``   O  (|V| x 2|E|) — densest-subgraph packing rows.
* ``InterweavedId``    W  (|E| x 2|E|) — densest-subgraph covering rows.

All of these are fully described by the edge list ``(u[k], v[k])`` of the
underlying graph — storing them explicitly would double (M) or quadruple
(O, W) the memory traffic. Products with the operator are segment
accumulations (scatter-add over endpoints); products with the transpose
are gathers (``w[u] + w[v]``), which is the direction the paper fuses.

TPU adaptation: the scatter direction of ``Incidence`` is a reduction
over its endpoint order (:func:`endpoint_order`, computed once per MWU
launch): the edge values gathered in vertex order, each vertex's run
summed by a segmented scan. It emits no XLA scatter and no per-call sort.
The other operators' scatter directions stay XLA scatter-adds. The
gather direction dispatches at
trace time through ``repro.kernels.dispatch`` — when the active
:class:`~repro.kernels.dispatch.KernelPolicy` selects the pallas
backend (``MWUOptions.kernel_backend``, resolved host-side by the solve
entry points), ``Incidence.rmatvec`` and ``VertexEdgePair.rmatvec`` run
the fused ``incidence_gather`` kernel (interpret mode on CPU CI, Mosaic
on TPU); under the default XLA policy they run the plain jnp gather
below, which doubles as the kernel's oracle. ``Transposed`` wrappers
ride along for free: vertex-cover's ``M^T`` gather is
``Transposed(Incidence).matvec`` = ``Incidence.rmatvec``.

Operators are registered pytrees, so they can be passed straight through
``jax.jit`` / ``lax.while_loop`` carries; shape metadata is static.

Conventions
-----------
* All operators are entrywise nonnegative (positive-LP requirement).
* ``matvec``:  (n,) -> (m,);  ``rmatvec``: (m,) -> (n,)  for an m x n op.
* ``colmax()`` returns per-column max entry (used for MWU's x init);
  ``colmax(row_scale)`` returns ``max_i row_scale[i] * A[i, j]`` which is
  what scaled wrappers need.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..kernels import dispatch as _kd

__all__ = [
    "LinOp",
    "Dense",
    "Coo",
    "Incidence",
    "EndpointOrder",
    "endpoint_order",
    "with_endpoint_order",
    "AdjacencyPlusId",
    "VertexEdgePair",
    "InterweavedId",
    "Transposed",
    "ScaledRows",
    "OnesRow",
    "VStack",
    "register_op",
]


def register_op(cls):
    """Register a LinOp dataclass as a pytree (array fields = leaves).

    Keyed registration: leaf paths render as attribute names
    (``.P.u`` rather than ``[<flat index 0>]``), which
    ``repro.api.stack_problems`` uses to name mismatched leaves.
    """
    fields = dataclasses.fields(cls)
    leaf_names = [f.name for f in fields if not f.metadata.get("static", False)]
    static_names = [f.name for f in fields if f.metadata.get("static", False)]

    def flatten_with_keys(op):
        return (
            tuple((jax.tree_util.GetAttrKey(n), getattr(op, n)) for n in leaf_names),
            tuple(getattr(op, n) for n in static_names),
        )

    def unflatten(aux, leaves):
        kwargs = dict(zip(leaf_names, leaves))
        kwargs.update(dict(zip(static_names, aux)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_with_keys(cls, flatten_with_keys, unflatten)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


class LinOp:
    """Abstract nonnegative linear operator."""

    #: (rows, cols)
    shape: tuple[int, int]

    def matvec(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def rmatvec(self, y: jax.Array) -> jax.Array:
        raise NotImplementedError

    def colmax(self, row_scale: jax.Array | None = None) -> jax.Array:
        raise NotImplementedError

    # nnz as stored (implicit ops report the implicit nonzero count)
    @property
    def nnz(self) -> int:
        raise NotImplementedError

    @property
    def T(self) -> "LinOp":
        return Transposed(self)

    def materialize(self) -> jax.Array:
        """Dense (m, n) matrix — for tests/small problems only."""
        n = self.shape[1]
        return jax.vmap(self.matvec, in_axes=1, out_axes=1)(jnp.eye(n))


@register_op
@dataclass
class Dense(LinOp):
    """Explicit dense matrix (tests, tiny LPs, scipy cross-checks)."""

    mat: jax.Array

    @property
    def shape(self):
        return tuple(self.mat.shape)

    def matvec(self, x):
        return self.mat @ x

    def rmatvec(self, y):
        return self.mat.T @ y

    def colmax(self, row_scale=None):
        m = self.mat if row_scale is None else self.mat * row_scale[:, None]
        return jnp.max(m, axis=0)

    @property
    def nnz(self):
        return int(np.prod(self.mat.shape))

    def materialize(self):
        return self.mat


@register_op
@dataclass
class Coo(LinOp):
    """Padded COO: the generic explicit-sparse fallback (the "PETSc" path).

    Padding entries must carry ``val == 0`` and any in-range indices.
    """

    rows: jax.Array  # (nnz,) int32
    cols: jax.Array  # (nnz,) int32
    vals: jax.Array  # (nnz,)
    _shape: tuple[int, int] = static_field(default=(0, 0))

    @property
    def shape(self):
        return self._shape

    def matvec(self, x):
        out = jnp.zeros((self._shape[0],), dtype=x.dtype)
        return out.at[self.rows].add(self.vals.astype(x.dtype) * x[self.cols])

    def rmatvec(self, y):
        out = jnp.zeros((self._shape[1],), dtype=y.dtype)
        return out.at[self.cols].add(self.vals.astype(y.dtype) * y[self.rows])

    def colmax(self, row_scale=None):
        v = self.vals
        if row_scale is not None:
            v = v * row_scale[self.rows]
        out = jnp.zeros((self._shape[1],), dtype=v.dtype)
        return out.at[self.cols].max(v)

    @property
    def nnz(self):
        return int(self.rows.shape[0])


ROW = 128  # slots per row of the segmented sum: one TPU vector row of lanes


class EndpointOrder(NamedTuple):
    """The 2E endpoint slots ``concat(u, v)`` of an edge list, sorted by vertex.

    * ``slot_edge`` (S,) int32: the edge whose value fills each slot. S is
      2E plus at least one pad slot, and a pad slot holds E, which reads 0;
    * ``starts`` (S,) bool: the slot begins a vertex's run (so does the
      first pad slot);
    * ``seg_end`` (n,) int32: each vertex's last slot; a vertex with no
      edge points at the first pad slot, whose run sums to 0.
    """

    slot_edge: jax.Array
    starts: jax.Array
    seg_end: jax.Array


@jax.named_scope("incidence.scatter")
def endpoint_order(u, v, n_vertices: int) -> EndpointOrder:
    """Sort the endpoints of ``(u, v)`` by vertex (stable: by slot within one).

    It serves the scatter direction alone, so its time counts under that
    direction's scope, once per launch.
    """
    E = u.shape[0]
    keys = jnp.concatenate([u, v]).astype(jnp.int32)
    order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    pad = -(-(2 * E + 1) // ROW) * ROW - 2 * E
    sorted_keys = jnp.concatenate([keys[order], jnp.full((pad,), n_vertices, jnp.int32)])
    slot_edge = jnp.concatenate([jnp.where(order < E, order, order - E), jnp.full((pad,), E, jnp.int32)])
    starts = jnp.concatenate([jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]])
    last = jnp.full((n_vertices,), -1, jnp.int32).at[sorted_keys[: 2 * E]].max(
        jnp.arange(2 * E, dtype=jnp.int32), indices_are_sorted=True
    )
    return EndpointOrder(slot_edge, starts, jnp.where(last < 0, 2 * E, last))


def _scan_rows(f, v):
    """Segmented inclusive sums along each row, by log2(ROW) lane shifts.

    Returns ``(started, sums)``: whether a run starts at or before each
    slot of its row, and the sum from that start (or the row's first slot).
    """
    k = 1
    while k < ROW:
        shift = ((0, 0), (k, 0))
        f_prev = jnp.pad(f[:, :-k], shift, constant_values=False)
        v_prev = jnp.pad(v[:, :-k], shift)
        v = jnp.where(f, v, v + v_prev)
        f = f | f_prev
        k *= 2
    return f, v


def segmented_sum(starts, vals, at):
    """The inclusive sums of ``vals`` (1-D) that restart wherever ``starts``, read at ``at``.

    Rows of :data:`ROW` slots are scanned along the row; the rows' last
    sums are scanned the same way, one level per factor of ROW, for the
    carry into a row's slots before its first start. Every partial sum
    restarts at a run's start, so a long run does not pay for the
    cancellation of a difference of prefix sums.
    """
    n = vals.shape[0]
    rows = -(-n // ROW)
    pad = rows * ROW - n
    # pin the rows row-major, which vmap keeps with its lanes major: left
    # to XLA, 4 vmapped lanes went minor, padded to 128 lanes, and the
    # scan moved 32 times its bytes (a v5e ran a Graph500-13 vertex
    # cover solve 77% slower than with the scatter-add)
    rowwise = Layout(major_to_minor=(0, 1))
    f = with_layout_constraint(jnp.pad(starts, (0, pad), constant_values=True).reshape(rows, ROW), rowwise)
    v = with_layout_constraint(jnp.pad(vals, (0, pad)).reshape(rows, ROW), rowwise)
    started, s = _scan_rows(f, v)
    row, lane = at // ROW, at % ROW
    out = s[row, lane]
    if rows > 1:
        total = segmented_sum(f.any(axis=1), s[:, -1], jnp.arange(rows - 1))
        carry = jnp.concatenate([jnp.zeros((1,), vals.dtype), total])
        out = out + jnp.where(started[row, lane], 0, carry[row])
    return out


def with_endpoint_order(op):
    """``op`` with every :class:`Incidence` inside it given its endpoint order.

    Walks any operator pytree (wrappers, stacks, transposes, the mesh's
    slab wrappers). Call it once per launch, outside the MWU loop, so the
    loop's scatter direction sorts nothing.
    """
    def add(o):
        if isinstance(o, Incidence) and o.order is None:
            return dataclasses.replace(o, order=endpoint_order(o.u, o.v, o.n_vertices))
        return o

    return jax.tree_util.tree_map(add, op, is_leaf=lambda o: isinstance(o, Incidence))


@register_op
@dataclass
class Incidence(LinOp):
    """Vertex-edge incidence matrix M (eq. 4): M[u, e] = 1 iff u in e.

    Stored implicitly as the edge list. Optional per-edge weights scale
    the column (both endpoints share the weight — weighted graphs).
    ``edge_mask`` zeroes padded edges (distributed layouts pad).
    ``order`` is the endpoint order of the scatter direction
    (:func:`with_endpoint_order`); without it ``matvec`` computes one in
    place.
    """

    u: jax.Array  # (E,) int32 endpoint 0
    v: jax.Array  # (E,) int32 endpoint 1
    n_vertices: int = static_field(default=0)
    weights: Any = None  # optional (E,)
    edge_mask: Any = None  # optional (E,) bool
    order: Any = None  # optional EndpointOrder

    @property
    def shape(self):
        return (self.n_vertices, int(self.u.shape[0]))

    def _w(self, dtype):
        E = self.u.shape[0]
        w = jnp.ones((E,), dtype) if self.weights is None else self.weights.astype(dtype)
        if self.edge_mask is not None:
            w = jnp.where(self.edge_mask, w, 0)
        return w

    @jax.named_scope("incidence.scatter")
    def matvec(self, x):
        # y_u += x_e ; y_v += x_e  (scatter direction), as a sum over each
        # vertex's run of edge values in endpoint order
        _kd.note_scatter(ordered=self.order is not None)
        order = self.order if self.order is not None else endpoint_order(self.u, self.v, self.n_vertices)
        xw = x * self._w(x.dtype)
        if xw.shape[0] == 0:  # take() refuses an empty axis; no edges sum to 0
            return jnp.zeros((self.n_vertices,), x.dtype)
        vals = jnp.take(xw, order.slot_edge, mode="fill", fill_value=0)
        return segmented_sum(order.starts, vals, order.seg_end)

    @jax.named_scope("incidence.gather")
    def rmatvec(self, y):
        # g_e = y_u + y_v  (gather direction — the Pallas hot spot)
        if _kd.choose("gather", y) == "pallas":
            g = _kd.gather_pallas(self.u, self.v, y)
        else:
            g = y[self.u] + y[self.v]
        return g * self._w(y.dtype)

    def colmax(self, row_scale=None):
        w = self._w(jnp.float32 if row_scale is None else row_scale.dtype)
        if row_scale is None:
            return w
        return jnp.maximum(row_scale[self.u], row_scale[self.v]) * w

    @property
    def nnz(self):
        return 2 * int(self.u.shape[0])


@register_op
@dataclass
class AdjacencyPlusId(LinOp):
    """(I + A) for dominating set (eq. 8). Symmetric; edges stored once."""

    u: jax.Array
    v: jax.Array
    n_vertices: int = static_field(default=0)
    edge_mask: Any = None

    @property
    def shape(self):
        return (self.n_vertices, self.n_vertices)

    def _mask(self, x, dtype):
        if self.edge_mask is None:
            return x
        return jnp.where(self.edge_mask, x, jnp.zeros((), dtype))

    def matvec(self, x):
        xu = self._mask(x[self.u], x.dtype)
        xv = self._mask(x[self.v], x.dtype)
        out = x  # identity part
        return out.at[self.u].add(xv).at[self.v].add(xu)

    def rmatvec(self, y):
        return self.matvec(y)  # symmetric

    def colmax(self, row_scale=None):
        if row_scale is None:
            return jnp.ones((self.n_vertices,), jnp.float32)
        # column j: entries at rows {j} ∪ N(j) -> max of row_scale there.
        out = row_scale  # identity entry
        su = self._mask(row_scale[self.u], row_scale.dtype)
        sv = self._mask(row_scale[self.v], row_scale.dtype)
        return out.at[self.u].max(sv).at[self.v].max(su)

    @property
    def nnz(self):
        return self.n_vertices + 2 * int(self.u.shape[0])


@register_op
@dataclass
class VertexEdgePair(LinOp):
    """Vertex-edge-pair matrix O (eq. 14): (|V| x 2|E|).

    Column 2e   has a 1 at row u for edge e = (u, v);
    column 2e+1 has a 1 at row v. Variables z are laid out interleaved,
    matching the paper's (13)/(14); we view z as (E, 2).
    """

    u: jax.Array
    v: jax.Array
    n_vertices: int = static_field(default=0)
    edge_mask: Any = None

    @property
    def shape(self):
        return (self.n_vertices, 2 * int(self.u.shape[0]))

    def _m(self, x, dtype):
        if self.edge_mask is None:
            return x
        return jnp.where(self.edge_mask, x, jnp.zeros((), dtype))

    def matvec(self, z):
        z2 = z.reshape(-1, 2)
        zu = self._m(z2[:, 0], z.dtype)
        zv = self._m(z2[:, 1], z.dtype)
        out = jnp.zeros((self.n_vertices,), dtype=z.dtype)
        return out.at[self.u].add(zu).at[self.v].add(zv)

    def rmatvec(self, y):
        if _kd.choose("gather", y) == "pallas":
            # Interleaved pair gather through the incidence kernel: with
            # idx = [u0, v0, u1, v1, ...], gather(idx, idx, y) = 2*y[idx]
            # and the halving is exact in binary floating point.
            idx = jnp.stack([self.u, self.v], axis=-1).reshape(-1)
            g = (0.5 * _kd.gather_pallas(idx, idx, y)).reshape(-1, 2)
        else:
            g = jnp.stack([y[self.u], y[self.v]], axis=-1)
        if self.edge_mask is not None:
            g = jnp.where(self.edge_mask[:, None], g, 0)
        return g.reshape(-1)

    def colmax(self, row_scale=None):
        E = int(self.u.shape[0])
        if row_scale is None:
            return jnp.ones((2 * E,), jnp.float32)
        return self.rmatvec(row_scale)

    @property
    def nnz(self):
        return 2 * int(self.u.shape[0])


@register_op
@dataclass
class InterweavedId(LinOp):
    """Interweaved identity W (eq. 13): (|E| x 2|E|), W[e, 2e] = W[e, 2e+1] = 1."""

    n_edges: int = static_field(default=0)
    edge_mask: Any = None

    @property
    def shape(self):
        return (self.n_edges, 2 * self.n_edges)

    def matvec(self, z):
        out = z.reshape(-1, 2).sum(axis=-1)
        if self.edge_mask is not None:
            out = jnp.where(self.edge_mask, out, 0)
        return out

    def rmatvec(self, y):
        if self.edge_mask is not None:
            y = jnp.where(self.edge_mask, y, 0)
        return jnp.repeat(y, 2, total_repeat_length=2 * self.n_edges)

    def colmax(self, row_scale=None):
        if row_scale is None:
            return jnp.ones((2 * self.n_edges,), jnp.float32)
        return self.rmatvec(row_scale)

    @property
    def nnz(self):
        return 2 * self.n_edges


@register_op
@dataclass
class Transposed(LinOp):
    """Lazy transpose wrapper (vertex cover uses M^T)."""

    inner: LinOp

    @property
    def shape(self):
        m, n = self.inner.shape
        return (n, m)

    def matvec(self, x):
        return self.inner.rmatvec(x)

    def rmatvec(self, y):
        return self.inner.matvec(y)

    def colmax(self, row_scale=None):
        # columns of A^T are rows of A: colmax_j = max_i s_i A^T[i,j]
        #                                        = max_i s_i A[j,i] -> rowmax of scaled A
        if row_scale is None:
            # max over each row of A == A @ onehot trick; use matvec with
            # (max,*) semiring replacement: for 0/1 implicit ops a row max is
            # 1 wherever the row is nonempty. Generic fallback:
            return _rowmax(self.inner, None)
        return _rowmax(self.inner, row_scale)

    @property
    def nnz(self):
        return self.inner.nnz


def _rowmax(op: LinOp, col_scale):
    """max_j op[i, j] * col_scale[j] for each row i (semiring max-product)."""
    if isinstance(op, Dense):
        m = op.mat if col_scale is None else op.mat * col_scale[None, :]
        return jnp.max(m, axis=1)
    if isinstance(op, Coo):
        v = op.vals if col_scale is None else op.vals * col_scale[op.cols]
        return jnp.zeros((op.shape[0],), v.dtype).at[op.rows].max(v)
    if isinstance(op, Incidence):
        w = op._w(jnp.float32 if col_scale is None else col_scale.dtype)
        cw = w if col_scale is None else w * col_scale
        out = jnp.zeros((op.n_vertices,), cw.dtype)
        return out.at[op.u].max(cw).at[op.v].max(cw)
    raise NotImplementedError(f"rowmax for {type(op).__name__}")


@register_op
@dataclass
class ScaledRows(LinOp):
    """diag(scale) @ inner — used to normalize b-vectors to all-ones."""

    scale: jax.Array  # (m,)
    inner: LinOp

    @property
    def shape(self):
        return self.inner.shape

    def matvec(self, x):
        return self.scale * self.inner.matvec(x)

    def rmatvec(self, y):
        return self.inner.rmatvec(self.scale * y)

    def colmax(self, row_scale=None):
        s = self.scale if row_scale is None else self.scale * row_scale
        return self.inner.colmax(s)

    @property
    def nnz(self):
        return self.inner.nnz


@register_op
@dataclass
class OnesRow(LinOp):
    """(1/M) * c^T as a single covering/packing row (objective embedding, §2.2)."""

    c: jax.Array  # (n,) nonnegative objective
    inv_bound: jax.Array  # scalar 1/M

    @property
    def shape(self):
        return (1, int(self.c.shape[0]))

    def matvec(self, x):
        return (self.inv_bound * jnp.dot(self.c, x))[None]

    def rmatvec(self, y):
        return self.inv_bound * self.c * y[0]

    def colmax(self, row_scale=None):
        s = self.inv_bound if row_scale is None else self.inv_bound * row_scale[0]
        return self.c * s

    @property
    def nnz(self):
        return int(self.c.shape[0])


@register_op
@dataclass
class VStack(LinOp):
    """Row-stack of operators sharing a column space."""

    ops: tuple  # tuple[LinOp, ...]

    @property
    def shape(self):
        return (sum(o.shape[0] for o in self.ops), self.ops[0].shape[1])

    def matvec(self, x):
        return jnp.concatenate([o.matvec(x) for o in self.ops])

    def rmatvec(self, y):
        out = None
        off = 0
        for o in self.ops:
            m = o.shape[0]
            r = o.rmatvec(jax.lax.dynamic_slice_in_dim(y, off, m))
            out = r if out is None else out + r
            off += m
        return out

    def colmax(self, row_scale=None):
        out = None
        off = 0
        for o in self.ops:
            m = o.shape[0]
            rs = None if row_scale is None else jax.lax.dynamic_slice_in_dim(row_scale, off, m)
            c = o.colmax(rs)
            out = c if out is None else jnp.maximum(out, c)
            off += m
        return out

    @property
    def nnz(self):
        return sum(o.nnz for o in self.ops)
