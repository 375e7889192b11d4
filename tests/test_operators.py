"""Implicit operators vs dense materialization (paper §5.1.2).

Property-based: on random graphs, every implicit operator must agree
with its explicit dense matrix for matvec, rmatvec and colmax.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property-based tests need the 'test' extra")
from hypothesis import given, settings, strategies as st

from repro.core import (
    AdjacencyPlusId,
    Coo,
    Incidence,
    InterweavedId,
    OnesRow,
    ScaledRows,
    Transposed,
    VertexEdgePair,
    VStack,
)
from repro.core.operators import with_endpoint_order
from repro.graphs import Graph


def random_graph(rng, n, m):
    e = rng.integers(0, n, size=(m, 2))
    g = Graph.from_edges(n, e)
    if g.m == 0:  # ensure at least one edge
        g = Graph.from_edges(n, np.array([[0, 1]]))
    return g


def dense_incidence(g):
    M = np.zeros((g.n, g.m))
    M[g.u, np.arange(g.m)] = 1
    M[g.v, np.arange(g.m)] = 1
    return M


def dense_adj_plus_id(g):
    A = np.eye(g.n)
    A[g.u, g.v] = 1
    A[g.v, g.u] = 1
    return A


def dense_vertex_edge_pair(g):
    O = np.zeros((g.n, 2 * g.m))
    O[g.u, 2 * np.arange(g.m)] = 1
    O[g.v, 2 * np.arange(g.m) + 1] = 1
    return O


def dense_interweaved(g):
    W = np.zeros((g.m, 2 * g.m))
    W[np.arange(g.m), 2 * np.arange(g.m)] = 1
    W[np.arange(g.m), 2 * np.arange(g.m) + 1] = 1
    return W


def check_against_dense(op, D, rng, atol=1e-10):
    m, n = D.shape
    assert op.shape == (m, n)
    x = rng.random(n)
    y = rng.random(m)
    np.testing.assert_allclose(np.asarray(op.matvec(jnp.asarray(x))), D @ x, atol=atol)
    np.testing.assert_allclose(np.asarray(op.rmatvec(jnp.asarray(y))), D.T @ y, atol=atol)
    np.testing.assert_allclose(
        np.asarray(op.colmax()), D.max(axis=0), atol=atol
    )
    s = rng.random(m) + 0.1
    np.testing.assert_allclose(
        np.asarray(op.colmax(jnp.asarray(s))), (D * s[:, None]).max(axis=0), atol=atol
    )


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 30), m=st.integers(1, 80))
def test_incidence_matches_dense(seed, n, m):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n, m)
    op = Incidence(u=jnp.asarray(g.u), v=jnp.asarray(g.v), n_vertices=g.n)
    check_against_dense(op, dense_incidence(g), rng)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 30), m=st.integers(1, 80))
def test_adj_plus_id_matches_dense(seed, n, m):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n, m)
    op = AdjacencyPlusId(u=jnp.asarray(g.u), v=jnp.asarray(g.v), n_vertices=g.n)
    D = dense_adj_plus_id(g)
    x = rng.random(g.n)
    np.testing.assert_allclose(np.asarray(op.matvec(jnp.asarray(x))), D @ x, atol=1e-10)
    np.testing.assert_allclose(np.asarray(op.rmatvec(jnp.asarray(x))), D.T @ x, atol=1e-10)
    s = rng.random(g.n) + 0.1
    np.testing.assert_allclose(
        np.asarray(op.colmax(jnp.asarray(s))), (D * s[:, None]).max(axis=0), atol=1e-10
    )


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 30), m=st.integers(1, 80))
def test_vertex_edge_pair_matches_dense(seed, n, m):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, n, m)
    op = VertexEdgePair(u=jnp.asarray(g.u), v=jnp.asarray(g.v), n_vertices=g.n)
    check_against_dense(op, dense_vertex_edge_pair(g), rng)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), m=st.integers(1, 40))
def test_interweaved_matches_dense(seed, m):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, max(3, m // 2 + 2), m)
    op = InterweavedId(n_edges=g.m)
    check_against_dense(op, dense_interweaved(g), rng)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_transposed_and_scaled(seed):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, 12, 30)
    M = dense_incidence(g)
    op = Incidence(u=jnp.asarray(g.u), v=jnp.asarray(g.v), n_vertices=g.n)
    check_against_dense(Transposed(op), M.T, rng)
    s = rng.random(g.n) + 0.25
    check_against_dense(ScaledRows(scale=jnp.asarray(s), inner=op), s[:, None] * M, rng)


def test_coo_and_vstack_and_onesrow():
    rng = np.random.default_rng(7)
    D = rng.random((6, 9)) * (rng.random((6, 9)) < 0.4)
    r, c = np.nonzero(D)
    op = Coo(rows=jnp.asarray(r, jnp.int32), cols=jnp.asarray(c, jnp.int32),
             vals=jnp.asarray(D[r, c]), _shape=D.shape)
    check_against_dense(op, D, rng)

    cvec = rng.random(9) + 0.1
    one = OnesRow(c=jnp.asarray(cvec), inv_bound=jnp.asarray(0.25))
    check_against_dense(one, 0.25 * cvec[None, :], rng)

    stk = VStack(ops=(op, one))
    check_against_dense(stk, np.vstack([D, 0.25 * cvec[None, :]]), rng)


def test_coo_padding_entries_are_inert():
    # padded entries: val 0, arbitrary in-range indices
    r = jnp.asarray([0, 1, 0], jnp.int32)
    c = jnp.asarray([0, 1, 0], jnp.int32)
    v = jnp.asarray([2.0, 3.0, 0.0])
    op = Coo(rows=r, cols=c, vals=v, _shape=(2, 2))
    x = jnp.asarray([1.0, 1.0])
    np.testing.assert_allclose(np.asarray(op.matvec(x)), [2.0, 3.0])
    np.testing.assert_allclose(np.asarray(op.rmatvec(x)), [2.0, 3.0])


def test_incidence_edge_mask():
    u = jnp.asarray([0, 1, 0], jnp.int32)
    v = jnp.asarray([1, 2, 2], jnp.int32)
    mask = jnp.asarray([True, True, False])
    op = Incidence(u=u, v=v, n_vertices=3, edge_mask=mask)
    x = jnp.ones(3)
    # masked edge contributes nothing
    np.testing.assert_allclose(np.asarray(op.matvec(x)), [1.0, 2.0, 1.0])
    np.testing.assert_allclose(np.asarray(op.rmatvec(jnp.asarray([1.0, 2.0, 4.0]))),
                               [3.0, 6.0, 0.0])


def test_materialize_roundtrip(small_graphs):
    g = small_graphs["triangle"]
    op = Incidence(u=jnp.asarray(g.u), v=jnp.asarray(g.v), n_vertices=g.n)
    np.testing.assert_allclose(np.asarray(op.materialize()), dense_incidence(g))


# -- the scatter direction as a reduction over the endpoint order -------------
N_HUB, E_HUB = 600, 12_000  # a third of the edges at vertex 0; the last 50 vertices have none


def _hub_edges(rng, extra=0):
    u = rng.integers(0, N_HUB - 50, E_HUB + extra)
    u[: E_HUB // 3] = 0
    v = rng.integers(1, N_HUB - 50, E_HUB + extra)
    return u.astype(np.int32), v.astype(np.int32)


def _loads_near_one(rng, u, v, shape=()):
    """f32 edge values whose vertex sums sit near 1 (MWU's packing loads)."""
    deg = np.bincount(u, minlength=N_HUB) + np.bincount(v, minlength=N_HUB)
    return (rng.uniform(0.5, 1.5, shape + u.shape) / np.maximum(deg[u], deg[v])).astype(np.float32)


def _dense_f64(u, v, w=None):
    """The incidence matrix in float64, columns scaled by ``w`` (repeats and self loops add)."""
    M = np.zeros((N_HUB, u.shape[0]))
    np.add.at(M, (u, np.arange(u.shape[0])), 1.0)
    np.add.at(M, (v, np.arange(u.shape[0])), 1.0)
    return M if w is None else M * w


def _xla_scatter(u, v, xw):
    """The XLA scatter-add the ordered reduction replaced: the error to match."""
    return jnp.zeros(xw.shape[:-1] + (N_HUB,), xw.dtype).at[..., u].add(xw).at[..., v].add(xw)


def _case(name, rng):
    """(the ordered matvec in f32, the old scatter in f32, float64 M @ x) on one input."""
    u, v = _hub_edges(rng)
    x = _loads_near_one(rng, u, v)
    matvec = jax.jit(lambda op, x: with_endpoint_order(op).matvec(x))
    if name == "isolated_vertices":
        return matvec(Incidence(u, v, n_vertices=N_HUB), x), _xla_scatter(u, v, x), _dense_f64(u, v) @ x
    if name == "repeated_edges":
        u[E_HUB // 3 : E_HUB // 2], v[E_HUB // 3 : E_HUB // 2] = 7, 11
        x = _loads_near_one(rng, u, v)
        return matvec(Incidence(u, v, n_vertices=N_HUB), x), _xla_scatter(u, v, x), _dense_f64(u, v) @ x
    if name == "edge_mask":
        up, vp = _hub_edges(rng, extra=1000)  # the last 1000 edges are padding, with large values
        up[E_HUB:], vp[E_HUB:] = 0, 1
        xp = np.concatenate([_loads_near_one(rng, up[:E_HUB], vp[:E_HUB]), np.full(1000, 1e3, np.float32)])
        mask = np.arange(E_HUB + 1000) < E_HUB
        op = Incidence(up, vp, n_vertices=N_HUB, edge_mask=jnp.asarray(mask))
        return matvec(op, xp), _xla_scatter(up, vp, np.where(mask, xp, 0)), _dense_f64(up, vp, mask) @ xp
    if name == "edge_weights":
        w = rng.uniform(0.1, 3.0, E_HUB).astype(np.float32)
        op = Incidence(u, v, n_vertices=N_HUB, weights=jnp.asarray(w))
        return matvec(op, x), _xla_scatter(u, v, x * w), _dense_f64(u, v, w.astype(np.float64)) @ x
    if name == "vmapped_lanes":
        X = _loads_near_one(rng, u, v, shape=(4,))
        op = with_endpoint_order(Incidence(u, v, n_vertices=N_HUB))
        y = jax.jit(jax.vmap(lambda x: op.matvec(x)))(X)
        return y, _xla_scatter(u, v, X), X @ _dense_f64(u, v).T
    if name == "stacked_problems":
        uv = [_hub_edges(rng) for _ in range(3)]
        U, V = (np.stack(a) for a in zip(*uv))
        X = np.stack([_loads_near_one(rng, a, b) for a, b in uv])
        y = jax.jit(jax.vmap(lambda op, x: with_endpoint_order(op).matvec(x)))(Incidence(U, V, n_vertices=N_HUB), X)
        old = np.stack([_xla_scatter(a, b, x) for (a, b), x in zip(uv, X)])
        return y, old, np.stack([_dense_f64(a, b) @ x for (a, b), x in zip(uv, X)])
    if name == "transposed_rmatvec":
        y = jax.jit(lambda op, x: with_endpoint_order(op).rmatvec(x))(Transposed(Incidence(u, v, n_vertices=N_HUB)), x)
        return y, _xla_scatter(u, v, x), _dense_f64(u, v) @ x
    assert name == "no_precomputed_order"
    op = Incidence(u, v, n_vertices=N_HUB)
    return jax.jit(lambda op, x: op.matvec(x))(op, x), _xla_scatter(u, v, x), _dense_f64(u, v) @ x


@pytest.mark.parametrize(
    "case",
    ["isolated_vertices", "repeated_edges", "edge_mask", "edge_weights", "vmapped_lanes",
     "stacked_problems", "transposed_rmatvec", "no_precomputed_order"],
)
def test_incidence_scatter_matches_float64(case):
    """The scatter direction, summed over each vertex's run in endpoint order,
    agrees with float64 ``M @ x`` at least as closely as XLA's f32 scatter-add:
    on loads near 1 with a hub of 4,000 edges, vertices with no edge read 0."""
    new, old, ref = _case(case, np.random.default_rng(16))
    new, old = np.asarray(new), np.asarray(old)
    assert new.dtype == np.float32 and new.shape == ref.shape
    err, old_err = np.abs(new - ref).max(), np.abs(old - ref).max()
    assert err <= old_err and err < 1e-5, (err, old_err)
    assert np.all(new[..., ref.any(axis=tuple(range(ref.ndim - 1))) == 0] == 0)
