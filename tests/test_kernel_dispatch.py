"""The kernels.dispatch backend-selection layer, end to end.

Four layers of coverage:

1. Policy resolution — explicit request vs env var vs platform default,
   and the per-op gates (f64-on-TPU, VMEM vertex limit, masks).
2. The custom_vmap pallas wrappers — unbatched calls match the oracles;
   vmapped calls take the batch rule and match vmapped oracles.
3. Operator / smoothing / stepsize wiring — pallas-policy results match
   the default XLA policy on the same inputs, including weighted,
   masked, and padded-edge-slot operators (which must fall back).
4. End-to-end ``solve(kernel_backend="pallas")`` vs ``"xla"`` on all
   four problem families, with dispatch stats proving the kernel path
   was genuinely active (not silently falling back).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as ops
from repro.core.smoothing import smax_and_weights, smin_and_weights
from repro.core.stepsize import make_probe_fn
from repro.graphs import build, grid2d
from repro.kernels import dispatch as kd

PALLAS = kd.KernelPolicy("pallas", interpret=True)


@pytest.fixture(autouse=True)
def _clean_stats():
    kd.reset_stats()
    yield
    kd.reset_stats()


# -- 1. policy resolution ---------------------------------------------------
def test_resolve_explicit_requests(monkeypatch):
    monkeypatch.delenv(kd.ENV_VAR, raising=False)
    assert kd.resolve("xla") == kd.XLA_POLICY
    pol = kd.resolve("pallas")
    assert pol.backend == "pallas"
    # interpret mode everywhere except a real TPU
    assert pol.interpret == (jax.default_backend() != "tpu")


def test_resolve_auto_follows_platform(monkeypatch):
    monkeypatch.delenv(kd.ENV_VAR, raising=False)
    pol = kd.resolve("auto")
    # XLA on every platform, TPU included, until a chip measurement shows
    # a kernel of the pack winning
    assert pol == kd.XLA_POLICY
    assert kd.resolve(None) == pol


def test_resolve_env_var_overrides_auto_but_not_explicit(monkeypatch):
    monkeypatch.setenv(kd.ENV_VAR, "pallas")
    assert kd.resolve("auto").backend == "pallas"
    assert kd.resolve("xla") == kd.XLA_POLICY
    monkeypatch.setenv(kd.ENV_VAR, "xla")
    assert kd.resolve("auto") == kd.XLA_POLICY


def test_resolve_rejects_unknown_backend(monkeypatch):
    with pytest.raises(ValueError, match="kernel backend"):
        kd.resolve("mosaic")
    monkeypatch.setenv(kd.ENV_VAR, "bogus")
    with pytest.raises(ValueError, match="kernel backend"):
        kd.resolve("auto")


def test_env_var_is_reread_per_resolve(monkeypatch):
    """Satellite fix: backend choice must never come from a stale cache."""
    monkeypatch.setenv(kd.ENV_VAR, "pallas")
    first = kd.resolve("auto")
    monkeypatch.setenv(kd.ENV_VAR, "xla")
    second = kd.resolve("auto")
    assert first.backend == "pallas" and second.backend == "xla"


def test_gate_default_policy_is_xla():
    x = jnp.ones(8)
    assert kd.active_policy() == kd.XLA_POLICY
    assert kd.choose("softmax", x) == "xla"
    with kd.use_policy(PALLAS):
        assert kd.choose("softmax", x) == "pallas"
    assert kd.choose("softmax", x) == "xla"  # scope restored
    s = kd.stats()
    assert s["softmax"] == {"pallas": 1, "xla": 2}


def test_gate_f64_requires_interpret():
    x64 = jnp.ones(8, jnp.float64)
    x32 = jnp.ones(8, jnp.float32)
    with kd.use_policy(kd.KernelPolicy("pallas", interpret=False)):
        assert kd.choose("softmax", x64) == "xla"  # no f64 VPU on real TPU
        assert kd.choose("softmax", x32) == "pallas"
    with kd.use_policy(PALLAS):
        assert kd.choose("softmax", x64) == "pallas"  # interpret keeps f64


def test_gate_gather_falls_back_on_real_tpu_with_reason():
    """The 1-D VMEM gather has no Mosaic lowering: a non-interpret policy
    sends every gather to XLA and says why in stats()."""
    x = jax.ShapeDtypeStruct((16,), jnp.float32)
    with kd.use_policy(kd.KernelPolicy("pallas", interpret=False)):
        assert kd.choose("gather", x) == "xla"
        assert kd.choose("softmax", x) == "pallas"
        assert kd.choose("softmax", jnp.ones(8, jnp.float64)) == "xla"
    s = kd.stats()
    assert s["gather"] == {
        "pallas": 0, "xla": 1, "fallback": {kd.GATHER_NO_TPU_LOWERING: 1}
    }
    assert s["softmax"] == {"pallas": 1, "xla": 1, "fallback": {kd.F64_ON_TPU: 1}}


def test_gate_gather_vmem_limit():
    assert kd.vmem_vertex_limit(jnp.float32) == kd.VMEM_VERTEX_LIMIT
    assert kd.vmem_vertex_limit(jnp.float64) == kd.VMEM_VERTEX_LIMIT // 2
    small = jax.ShapeDtypeStruct((16,), jnp.float32)
    big = jax.ShapeDtypeStruct((kd.VMEM_VERTEX_LIMIT + 1,), jnp.float32)
    with kd.use_policy(PALLAS):
        assert kd.choose("gather", small) == "pallas"
        assert kd.choose("gather", big) == "xla"
        # non-gather ops stream in tiles and have no vertex cap
        assert kd.choose("axpy", big) == "pallas"


# -- 2. the custom_vmap wrappers -------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_wrappers_match_oracles(dtype):
    rng = np.random.default_rng(0)
    n = 257
    y = jnp.asarray(rng.random(n), dtype)
    dy = jnp.asarray(rng.random(n) * 1e-2, dtype)
    u = jnp.asarray(rng.integers(0, n, 400), jnp.int32)
    v = jnp.asarray(rng.integers(0, n, 400), jnp.int32)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    with kd.use_policy(PALLAS):
        g = kd.gather_pallas(u, v, y)
        lse, w = kd.softmax_pallas(y, 50.0, sign=-1.0)
        pl, ps, pm = kd.probe_pallas(y, dy, 2.0, 50.0, sign=1.0)
        ax, mn, mx = kd.axpy_pallas(y, dy, 2.0)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(y[u] + y[v]))
    a = -50.0 * np.asarray(y, np.float64)
    np.testing.assert_allclose(float(lse), np.log(np.exp(a - a.max()).sum()) + a.max(), rtol=tol)
    np.testing.assert_allclose(float(w.sum()), 1.0, rtol=tol)
    yv = np.asarray(y, np.float64) + 2.0 * np.asarray(dy, np.float64)
    e = np.exp(50.0 * yv - (50.0 * yv).max())
    np.testing.assert_allclose(float(pl), np.log(e.sum()) + (50.0 * yv).max(), rtol=tol)
    np.testing.assert_allclose(float(ps), (e * np.asarray(dy, np.float64)).sum() / e.sum(), rtol=tol)
    np.testing.assert_allclose(float(pm), yv.min(), rtol=tol)
    np.testing.assert_allclose(np.asarray(ax), yv, rtol=tol)
    assert float(mn) == pytest.approx(yv.min(), rel=tol)
    assert float(mx) == pytest.approx(yv.max(), rel=tol)


def test_wrappers_under_vmap_use_batch_rule():
    """vmapped lanes must not hit pallas_call; they take the XLA rule."""
    rng = np.random.default_rng(1)
    B, n, E = 3, 64, 100
    ys = jnp.asarray(rng.random((B, n)))
    dys = jnp.asarray(rng.random((B, n)) * 1e-2)
    u = jnp.asarray(rng.integers(0, n, E), jnp.int32)
    v = jnp.asarray(rng.integers(0, n, E), jnp.int32)
    alphas = jnp.asarray(rng.random(B))
    with kd.use_policy(PALLAS):
        # unbatched index args, batched vector arg
        g = jax.vmap(lambda w: kd.gather_pallas(u, v, w))(ys)
        lse, w = jax.vmap(lambda x: kd.softmax_pallas(x, 30.0, sign=1.0))(ys)
        pr = jax.vmap(lambda y, dy, a: kd.probe_pallas(y, dy, a, 30.0, sign=-1.0))(
            ys, dys, alphas
        )
        ax = jax.vmap(lambda y, dy, a: kd.axpy_pallas(y, dy, a))(ys, dys, alphas)
    assert g.shape == (B, E) and lse.shape == (B,) and w.shape == (B, n)
    assert pr[0].shape == (B,) and ax[0].shape == (B, n)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(ys[:, u] + ys[:, v]))
    for b in range(B):
        a = 30.0 * np.asarray(ys[b], np.float64)
        np.testing.assert_allclose(
            float(lse[b]), np.log(np.exp(a - a.max()).sum()) + a.max(), rtol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(ax[0][b]), np.asarray(ys[b] + alphas[b] * dys[b]), rtol=1e-12
        )


# -- 3. operator / smoothing / stepsize wiring -----------------------------
def _incidence(E=300, n=97, seed=2, weights=False, mask=False):
    rng = np.random.default_rng(seed)
    kw = {}
    if weights:
        kw["weights"] = jnp.asarray(rng.random(E) + 0.5)
    if mask:
        kw["edge_mask"] = jnp.asarray(rng.random(E) > 0.25)  # padded slots off
    return ops.Incidence(
        u=jnp.asarray(rng.integers(0, n, E), jnp.int32),
        v=jnp.asarray(rng.integers(0, n, E), jnp.int32),
        n_vertices=n,
        **kw,
    )


@pytest.mark.parametrize("weights", [False, True])
@pytest.mark.parametrize("mask", [False, True])
def test_incidence_rmatvec_parity(weights, mask):
    M = _incidence(weights=weights, mask=mask)
    y = jnp.asarray(np.random.default_rng(3).random(M.n_vertices))
    ref = M.rmatvec(y)
    assert kd.stats().get("gather", {}).get("pallas", 0) == 0
    with kd.use_policy(PALLAS):
        got = M.rmatvec(y)
    assert kd.stats()["gather"]["pallas"] == 1
    # same gather, same weight/mask multiply: bit-identical
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("mask", [False, True])
def test_vertex_edge_pair_rmatvec_parity(mask):
    rng = np.random.default_rng(4)
    E, n = 200, 63
    O = ops.VertexEdgePair(
        u=jnp.asarray(rng.integers(0, n, E), jnp.int32),
        v=jnp.asarray(rng.integers(0, n, E), jnp.int32),
        n_vertices=n,
        edge_mask=jnp.asarray(rng.random(E) > 0.3) if mask else None,
    )
    y = jnp.asarray(rng.random(n))
    ref = O.rmatvec(y)
    with kd.use_policy(PALLAS):
        got = O.rmatvec(y)
    # interleaved pair-gather: 0.5 * (y[i] + y[i]) is exact
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_transposed_incidence_matvec_parity():
    """Vertex cover's C = M^T: matvec routes through Incidence.rmatvec."""
    M = _incidence()
    y = jnp.asarray(np.random.default_rng(5).random(M.n_vertices))
    ref = M.T.matvec(y)
    with kd.use_policy(PALLAS):
        got = M.T.matvec(y)
    assert kd.stats()["gather"]["pallas"] == 1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_smoothing_parity_and_mask_fallback(dtype):
    rng = np.random.default_rng(6)
    v = jnp.asarray(rng.random(500), dtype)
    eta = jnp.asarray(80.0, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    sx_ref, wx_ref = smax_and_weights(v, eta)
    sn_ref, wn_ref = smin_and_weights(v, eta)
    mask = jnp.asarray(rng.random(500) > 0.5)
    kd.reset_stats()  # the reference calls above ticked the xla counter
    with kd.use_policy(PALLAS):
        sx, wx = smax_and_weights(v, eta)
        sn, wn = smin_and_weights(v, eta)
        sm, wm = smax_and_weights(v, eta, where=mask)
    s = kd.stats()["softmax"]
    assert s["pallas"] == 2  # the two unmasked calls
    assert s["xla"] == 0  # masked call never reaches choose(): hard fallback
    np.testing.assert_allclose(float(sx), float(sx_ref), rtol=tol)
    np.testing.assert_allclose(np.asarray(wx), np.asarray(wx_ref), atol=tol)
    np.testing.assert_allclose(float(sn), float(sn_ref), rtol=tol)
    np.testing.assert_allclose(np.asarray(wn), np.asarray(wn_ref), atol=tol)
    sm_ref, wm_ref = smax_and_weights(v, eta, where=mask)
    np.testing.assert_array_equal(np.asarray(wm), np.asarray(wm_ref))
    assert float(sm) == float(sm_ref)


def test_probe_fn_parity_and_mask_fallback():
    rng = np.random.default_rng(7)
    m, k = 300, 200
    y = jnp.asarray(rng.random(m))
    z = jnp.asarray(rng.random(k))
    dy = jnp.asarray(rng.random(m) * 1e-3)
    dz = jnp.asarray(rng.random(k) * 1e-3)
    eta = 60.0
    alpha = jnp.asarray(5.0)
    # with_grad: the XLA path leaves dphi/dpsi at 0 unless asked; the
    # kernel path always gets the Newton slopes for free
    ref = make_probe_fn(y, z, dy, dz, eta, with_grad=True)(alpha)
    kd.reset_stats()
    with kd.use_policy(PALLAS):
        got = make_probe_fn(y, z, dy, dz, eta)(alpha)
        c_mask = jnp.asarray(rng.random(k) > 0.5)
        masked = make_probe_fn(y, z, dy, dz, eta, c_mask=c_mask)(alpha)
    assert kd.stats()["probe"]["pallas"] == 1  # one probe_fn construction
    for a, b in zip(got, ref):
        np.testing.assert_allclose(float(a), float(b), rtol=1e-9, atol=1e-12)
    ref_masked = make_probe_fn(y, z, dy, dz, eta, c_mask=c_mask)(alpha)
    for a, b in zip(masked, ref_masked):
        assert float(a) == float(b)  # masked path is untouched XLA code


@pytest.mark.parametrize("alpha", [1.0, 64.0, 1e4, 1e6])
def test_probe_fn_f32_matches_f64(alpha):
    """Psi/Phi keep f32's relative precision for small and large steps.

    With eta*y near 900 and tiny step images, lse(eta(y + a dy)) - lse(eta y)
    is ~1e-6 of lse: taken as a plain difference in f32, Psi is 3% off at
    a = 1. Large steps (a = 1e6, increments past 0.7) take the plain
    difference again, which is then exact to ~1e-4.
    """
    rng = np.random.default_rng(3)
    m = 4096
    state = [rng.uniform(0.5, 1.0, m), rng.uniform(0.2, 0.9, m)]
    state += [rng.uniform(0.0, 1e-6, m), rng.uniform(0.0, 1e-6, m)]
    eta = 10 * np.log(2 * m) / 0.1
    got, ref = (
        make_probe_fn(*(jnp.asarray(a, dt) for a in state), jnp.asarray(eta, dt))(
            jnp.asarray(alpha, dt)
        )
        for dt in (jnp.float32, jnp.float64)
    )
    for name in ("psi", "phi", "f"):
        np.testing.assert_allclose(
            float(getattr(got, name)), float(getattr(ref, name)), rtol=1e-3, err_msg=name
        )


@pytest.mark.parametrize("interpret", [True, False])
def test_pallas_probe_f32_matches_f64(interpret):
    """Under a pallas policy an f32 probe takes the XLA path, whose Psi/Phi
    do not cancel, and dispatch.stats() says why; f64 keeps the kernel in
    interpret mode. The state is test_probe_fn_f32_matches_f64's at a = 1,
    where the kernel's difference of two lse is 3% off in f32."""
    rng = np.random.default_rng(3)
    m = 4096
    state = [rng.uniform(0.5, 1.0, m), rng.uniform(0.2, 0.9, m)]
    state += [rng.uniform(0.0, 1e-6, m), rng.uniform(0.0, 1e-6, m)]
    eta = 10 * np.log(2 * m) / 0.1

    def probe(dt):
        return make_probe_fn(*(jnp.asarray(a, dt) for a in state), jnp.asarray(eta, dt))(
            jnp.asarray(1.0, dt)
        )

    ref = probe(jnp.float64)
    kd.reset_stats()
    with kd.use_policy(kd.KernelPolicy("pallas", interpret=interpret)):
        got = probe(jnp.float32)
        f32_stats = kd.stats()["probe"]
        kd.reset_stats()
        probe(jnp.float64)
        f64_stats = kd.stats()["probe"]
    assert f32_stats == {"pallas": 0, "xla": 1, "fallback": {kd.PROBE_CANCELS: 1}}
    assert f64_stats["pallas"] == (1 if interpret else 0)
    for name in ("psi", "phi", "f"):
        np.testing.assert_allclose(
            float(getattr(got, name)), float(getattr(ref, name)), rtol=1e-3, err_msg=name
        )


# -- 4. end to end ----------------------------------------------------------
FAMILIES = ["match", "vcover", "dom-set", "dense-sub"]


@pytest.mark.parametrize("family", FAMILIES)
def test_solve_pallas_matches_xla(family):
    from repro.api import MWUOptions, Solver

    prob = build(family, grid2d(4))
    sols, stats = {}, {}
    for be in ["xla", "pallas"]:
        kd.reset_stats()
        opts = MWUOptions(eps=0.15, step_rule="newton", max_iter=20000, kernel_backend=be)
        sols[be] = Solver(opts, batch_width=4).solve(prob)
        stats[be] = kd.stats()
    a, b = sols["xla"], sols["pallas"]
    assert a.feasible and b.feasible
    # the certified binary-search bound is a discrete quantity: identical
    assert b.bound == pytest.approx(a.bound, rel=1e-5)
    # objectives agree at the eps guarantee level (trajectories may
    # diverge in ulps through the branchy step-size search)
    assert b.objective == pytest.approx(a.objective, rel=2 * opts.eps)
    # xla run must not touch pallas; pallas run must be genuinely active
    assert all(d["pallas"] == 0 for d in stats["xla"].values())
    sp = stats["pallas"]
    active = {"softmax", "probe", "axpy"}
    if family != "dom-set":  # dom-set's ops are scatter-based (no gather)
        active.add("gather")
    for op in active:
        assert sp[op]["pallas"] > 0, (family, op, sp)
        assert sp[op]["xla"] == 0, (family, op, sp)


def test_solve_batch_pallas_backend_vmaps():
    """solve_batch vmaps the whole loop; pallas backend must still work."""
    from repro.api import MWUOptions
    from repro.api.solver import _feasibility_batch

    prob = build("match", grid2d(4))
    out = {}
    for be in ["xla", "pallas"]:
        opts = MWUOptions(eps=0.2, step_rule="newton", max_iter=5000, kernel_backend=be)
        kernels = kd.resolve(be)
        res = _feasibility_batch(
            prob, jnp.asarray([4.0, 8.0, 12.0, 16.0]), opts, None, kernels=kernels
        )
        out[be] = np.asarray(res.status)
    # batched lanes share the vmapped XLA rule → identical feasibility calls
    np.testing.assert_array_equal(out["pallas"], out["xla"])


@pytest.mark.parametrize("family", ["match", "vcover"])
def test_every_in_loop_incidence_scatter_is_ordered(family):
    """A solve traces each incidence scatter direction over the endpoint order
    sorted once per launch (``ordered``); a product outside a solve sorts in
    place (``inline``)."""
    from repro.api import MWUOptions, Solver

    jax.clear_caches()  # the counts are taken at trace time
    prob = build(family, grid2d(5))
    sol = Solver(MWUOptions(eps=0.2, step_rule="newton", max_iter=5000), batch_width=2).solve(prob)
    assert sol.feasible
    assert set(kd.stats()["scatter"]["order"]) == {"ordered"}
    kd.reset_stats()
    inc = prob.P if family == "match" else prob.C.inner
    assert isinstance(inc, ops.Incidence)
    inc.matvec(jnp.ones(inc.shape[1]))
    assert kd.stats()["scatter"]["order"] == {"inline": 1}
