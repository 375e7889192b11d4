"""repro.dist — mesh-sharded solver layer tests.

Two tiers, mirroring tests/test_distributed.py's isolation rule:

* in-process: ``MeshPlan(1, 1)`` runs on the session's single device and
  must be BIT-identical to the plain ``Solver`` (the identity-plan
  contract), plus host-side plumbing (plan validation, mode selection,
  ``MeshPlan.shard_map`` kwargs).
* subprocess: each multi-device test spawns a fresh python with
  ``--xla_force_host_platform_device_count`` so the main session keeps
  its single device; pod-sharded runs are compared to the single-device
  oracle on solution *quality* (status + certificates) — psum
  re-association forks the line-search trajectory, so pointwise x
  equality is not expected (nor required by the paper's MPI runs).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(code: str, devices: int = 8, timeout=900, retries: int = 2):
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        f"import sys; sys.path.insert(0, {SRC!r})\n" + textwrap.dedent(code)
    )
    for attempt in range(retries + 1):
        res = subprocess.run(
            [sys.executable, "-c", prog], capture_output=True, text=True, timeout=timeout
        )
        if res.returncode == 0:
            return res.stdout
        # XLA-CPU collectives busy-wait; retry spurious rendezvous timeouts.
        if "rendezvous" not in res.stderr.lower() or attempt == retries:
            assert res.returncode == 0, f"stderr:\n{res.stderr[-3000:]}"
    return res.stdout


# ---------------------------------------------------------- in-process ----
def _families(g):
    from repro.graphs.problems import (
        densest_subgraph_lp,
        domset_lp,
        matching_lp,
        vcover_lp,
    )

    return [
        (matching_lp(g), [2.0, 5.0, 9.0]),
        (vcover_lp(g), [10.0, 25.0]),
        (domset_lp(g), [5.0, 15.0]),
        (densest_subgraph_lp(g), [2.0, 4.0]),
    ]


def test_identity_plan_bitparity_solve_batch():
    """MeshPlan(1,1) results are bit-identical to Solver.solve_batch."""
    from repro.api import Solver
    from repro.dist import DistSolver, MeshPlan
    from repro.graphs.generators import erdos

    g = erdos(40, 120, seed=0)
    dist = DistSolver(plan=MeshPlan(1, 1))
    for prob, bounds in _families(g):
        ref = Solver().solve_batch(prob, bounds)
        got = dist.solve_batch(prob, bounds)
        for f in ref._fields:
            a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(got, f))
            assert np.array_equal(a, b), f"{prob.name}.{f} not bit-identical"


def test_identity_plan_bitparity_feasibility_problem():
    """bound_mode='none' (gen-match) also bit-matches on the identity plan."""
    from repro.api import Solver
    from repro.dist import DistSolver, MeshPlan
    from repro.graphs.generators import erdos
    from repro.graphs.problems import generalized_matching_problem

    g = erdos(30, 80, seed=2)
    lb = np.zeros(g.n)
    ub = np.full(g.n, 2.0)
    prob = generalized_matching_problem(g, lb, ub)
    ref = Solver().solve_batch(prob, [1.0])
    got = DistSolver(plan=MeshPlan(1, 1)).solve_batch(prob, [1.0])
    for f in ref._fields:
        assert np.array_equal(np.asarray(getattr(ref, f)), np.asarray(getattr(got, f))), f


def test_identity_plan_solve_parity():
    """The inherited bound-search driver returns the identical Solution."""
    from repro.api import Solver
    from repro.dist import DistSolver, MeshPlan
    from repro.graphs.generators import erdos
    from repro.graphs.problems import matching_lp

    prob = matching_lp(erdos(40, 120, seed=0))
    ref = Solver().solve(prob)
    got = DistSolver(plan=MeshPlan(1, 1)).solve(prob)
    assert got.status == ref.status
    assert got.objective == ref.objective
    assert got.bound == ref.bound
    assert got.feasibility_calls == ref.feasibility_calls
    np.testing.assert_array_equal(got.x, ref.x)


def test_mesh_plan_validation():
    from repro.dist import MeshPlan

    with pytest.raises(ValueError, match=">= 1"):
        MeshPlan(pod=0)
    with pytest.raises(ValueError, match=">= 1"):
        MeshPlan(data=-1)
    # more devices than the host exposes -> actionable error at build()
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        MeshPlan(pod=64, data=64).build()
    # identity plan builds and is cached
    plan = MeshPlan()
    assert plan.build() is plan.build()
    assert plan.n_devices == 1 and plan.axes == ("pod", "data")


def test_pod_mode_selection():
    from repro.dist import pod_mode
    from repro.graphs.generators import erdos
    from repro.graphs.problems import (
        densest_subgraph_lp,
        domset_lp,
        matching_lp,
        vcover_lp,
    )

    g = erdos(20, 40, seed=0)
    assert pod_mode(matching_lp(g)) == "edge_slab"  # the paper's scheme
    assert pod_mode(vcover_lp(g)) == "column"
    assert pod_mode(domset_lp(g)) == "column"
    assert pod_mode(densest_subgraph_lp(g)) == "column"


def test_slab_pad_problem():
    from repro.dist import slab_pad_problem
    from repro.graphs.generators import erdos
    from repro.graphs.problems import matching_lp

    prob = matching_lp(erdos(30, 77, seed=1))  # 77 % 4 != 0
    padded, ncols = slab_pad_problem(prob, 4)
    assert ncols == 77
    E_pad = int(padded.P.u.shape[-1])
    assert E_pad % 4 == 0 and E_pad >= 77
    mask = np.asarray(padded.P.edge_mask)
    assert mask[:77].all() and not mask[77:].any()
    assert np.asarray(padded.c)[77:].sum() == 0
    # pod=1 is the identity (no padding, same object)
    same, n = slab_pad_problem(prob, 1)
    assert n == 77 and same is prob


def test_compat_shard_map_kwargs():
    """MeshPlan.shard_map runs with the replication check on, off and at its default."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist import MeshPlan

    plan = MeshPlan(1, 1)

    def body(x):
        return x * 2

    for kw in ({"check_vma": False}, {"check_vma": True}, {}):
        f = plan.shard_map(body, in_specs=(P(),), out_specs=P(), **kw)
        out = jax.jit(f)(jnp.arange(4.0))
        np.testing.assert_array_equal(np.asarray(out), np.arange(4.0) * 2)


# ---------------------------------------------------------- subprocess ----
def test_multi_device_parity():
    """8 virtual devices: edge-slab, column and combined pod x data plans
    all match the single-device oracle on status + certificates."""
    out = run_sub(
        """
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.graphs.generators import erdos
        from repro.graphs.problems import matching_lp, vcover_lp, domset_lp
        from repro.api import Solver
        from repro.dist import DistSolver, MeshPlan

        g = erdos(60, 201, seed=1)  # E=201: not divisible by 8 -> slab padding
        cases = [
            ("match-pod8", matching_lp(g), [3.0, 7.0, 12.0, 20.0], MeshPlan(pod=8, data=1)),
            ("match-pod2data4", matching_lp(g), [3.0, 7.0, 12.0, 20.0], MeshPlan(pod=2, data=4)),
            ("match-data8", matching_lp(g), [3.0, 7.0, 12.0, 20.0], MeshPlan(pod=1, data=8)),
            ("vcover-pod8", vcover_lp(g), [15.0, 40.0], MeshPlan(pod=8, data=1)),
            ("domset-pod4data2", domset_lp(g), [6.0, 18.0], MeshPlan(pod=4, data=2)),
        ]
        rows = {}
        for name, prob, bounds, plan in cases:
            ref = Solver().solve_batch(prob, bounds)
            dst = DistSolver(plan=plan).solve_batch(prob, bounds)
            # recompute certificates from the returned x: catches any
            # slab-reassembly/ordering bug independent of trajectory noise
            recheck = []
            for j, b in enumerate(bounds):
                P, C, pm, cm = prob.instantiate(float(b))
                x = jnp.asarray(np.asarray(dst.x)[j])
                px = np.asarray(P.matvec(x)); cx = np.asarray(C.matvec(x))
                if pm is not None: px = px[np.asarray(pm)]
                if cm is not None: cx = cx[np.asarray(cm)]
                recheck.append([float(px.max()), float(cx.min())])
            rows[name] = {
                "ref_status": np.asarray(ref.status).tolist(),
                "dst_status": np.asarray(dst.status).tolist(),
                "ref_max_px": np.asarray(ref.max_px).tolist(),
                "dst_max_px": np.asarray(dst.max_px).tolist(),
                "ref_min_cx": np.asarray(ref.min_cx).tolist(),
                "dst_min_cx": np.asarray(dst.min_cx).tolist(),
                "recheck": recheck,
            }
        print(json.dumps(rows))
        """,
        devices=8,
    )
    rows = json.loads(out.strip().splitlines()[-1])
    eps = 0.1  # MWUOptions() default
    for name, d in rows.items():
        assert d["dst_status"] == d["ref_status"], name
        got = np.asarray(d["recheck"])
        np.testing.assert_allclose(got[:, 0], d["dst_max_px"], rtol=1e-4, atol=1e-5, err_msg=name)
        np.testing.assert_allclose(got[:, 1], d["dst_min_cx"], rtol=1e-4, atol=1e-5, err_msg=name)
        for j, status in enumerate(d["dst_status"]):
            if status == 1:  # Status.FEASIBLE
                # The pod psums re-associate sums, so a feasible lane's
                # trajectory forks from the oracle's by rounding alone: it
                # starts 1e-15 apart and the gap grows ~10x every 5
                # iterations (test_column_slab_tracks_oracle_until_rounding),
                # so the lanes exit at different points (vcover-pod8:
                # max_px 0.957 vs 0.968). What both must meet is the
                # certificate, recomputed from x: Px <= 1+eps and Cx >= 1.
                assert got[j, 0] <= 1.0 + eps + 1e-5, (name, j, got[j])
                assert got[j, 1] >= 1.0 - 1e-5, (name, j, got[j])
            else:
                np.testing.assert_allclose(
                    d["dst_max_px"][j], d["ref_max_px"][j], rtol=5e-3, atol=5e-3, err_msg=name
                )
                np.testing.assert_allclose(
                    d["dst_min_cx"][j], d["ref_min_cx"][j], rtol=5e-3, atol=5e-3, err_msg=name
                )
    # the pure data fan-out runs the same per-lane program (unbatched on
    # each device vs vmapped in the oracle) — certificates must agree to
    # f32 fusion round-off, an order tighter than pod trajectory noise
    d = rows["match-data8"]
    np.testing.assert_allclose(d["dst_max_px"], d["ref_max_px"], rtol=1e-4)


def test_column_slab_tracks_oracle_until_rounding():
    """vcover on a pod=8 column slab follows the single-device trajectory
    to f64 round-off over the first iterations: the two differ only by the
    order of the psum'd sums, and that gap grows slowly (no slab bug)."""
    out = run_sub(
        """
        import json
        import jax
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.api import MWUOptions, Solver
        from repro.dist import DistSolver, MeshPlan
        from repro.graphs.generators import erdos
        from repro.graphs.problems import vcover_lp

        prob = vcover_lp(erdos(60, 201, seed=1))
        gaps = []
        for it in (1, 5, 10):
            o = MWUOptions(max_iter=it)
            ref = np.asarray(Solver(o).solve_batch(prob, [40.0]).x)
            dst = np.asarray(DistSolver(o, plan=MeshPlan(pod=8, data=1)).solve_batch(prob, [40.0]).x)
            gaps.append(float(np.abs(ref - dst).max() / np.abs(ref).max()))
        print(json.dumps(gaps))
        """,
        devices=8,
    )
    gaps = json.loads(out.strip().splitlines()[-1])
    assert gaps[0] < 1e-14, gaps  # one iteration: round-off only
    assert gaps[2] < 1e-9, gaps  # ten iterations: still far below any bug
    assert gaps[0] <= gaps[1] <= gaps[2], gaps  # the gap only grows


def test_lpserve_mesh_sharded_lanes():
    """LPEngine on a (2,2) plan: same answers as the sequential engine on
    mixed-size (bucket-padded, masked) graphs + per-device mesh stats."""
    out = run_sub(
        """
        import json
        import numpy as np
        from repro.graphs.generators import erdos
        from repro.graphs.problems import matching_lp, vcover_lp
        from repro.dist import MeshPlan
        from repro.lpserve import LPEngine, LPServeConfig

        probs = [matching_lp(erdos(30 + 10 * i, 80 + 25 * i, seed=i), name="match")
                 for i in range(5)]
        probs += [vcover_lp(erdos(40, 110, seed=9))]

        ref = LPEngine(LPServeConfig(lanes=4)).solve_many(probs)
        eng = LPEngine(LPServeConfig(lanes=4, mesh=MeshPlan(pod=2, data=2)))
        sols = eng.solve_many(probs)
        st = eng.stats()
        print(json.dumps({
            "ref": [[s.feasible, s.objective] for s in ref],
            "dst": [[s.feasible, s.objective] for s in sols],
            "mesh": st["mesh"],
            "completed": st["completed"],
        }))
        """,
        devices=4,
    )
    d = json.loads(out.strip().splitlines()[-1])
    assert d["completed"] == 6
    for (rf, ro), (df, do) in zip(d["ref"], d["dst"]):
        assert rf == df
        if rf:
            np.testing.assert_allclose(do, ro, rtol=0.1)
    mesh = d["mesh"]
    assert mesh["devices"] == 4 and mesh["pod"] == 2 and mesh["data"] == 2
    assert mesh["lanes_per_device"] == 2
    assert mesh["dist_launches"] > 0
    assert mesh["psum_rounds"] > 0  # pod sharding actually communicated


def test_place_puts_one_edge_slab_per_device():
    """A pod=4 solve leaves each device only its own edge slab, places the
    problem once for the whole bound search, and returns x and the
    objective over the original edges."""
    out = run_sub(
        """
        import json
        import numpy as np
        from repro.api import MWUOptions
        from repro.dist import DistSolver, MeshPlan
        from repro.graphs.generators import erdos
        from repro.graphs.problems import matching_lp

        prob = matching_lp(erdos(60, 201, seed=1))
        solver = DistSolver(MWUOptions(), plan=MeshPlan(pod=4, data=1))
        sol = solver.solve(prob)
        given, _, placed, ncols = solver._placed
        u = placed.P.u
        again = solver._prepare_launch(prob, [1.0, 2.0], False)["problem"]
        x = np.asarray(sol.x)
        print(json.dumps({
            "given": given is prob, "reused": again is placed, "ncols": ncols,
            "u_shards": sorted(s.data.shape[0] for s in u.addressable_shards),
            "u_devices": len({s.device for s in u.addressable_shards}),
            "calls": sol.feasibility_calls, "x_len": len(x),
            "objective": [sol.objective, float(np.asarray(prob.c) @ x)],
        }))
        """,
        devices=4,
    )
    d = json.loads(out.strip().splitlines()[-1])
    assert d["u_shards"] == [51] * 4 and d["u_devices"] == 4  # 201 edges padded to 204
    assert d["given"] and d["reused"] and d["calls"] > 1
    assert d["ncols"] == 201 and d["x_len"] == 201
    assert d["objective"][0] == pytest.approx(d["objective"][1], rel=1e-6)


def test_pallas_pack_active_under_shard_map():
    """The no-vmap fast path keeps the fused Pallas kernels (interpret
    mode on CPU) on the hot path inside shard_map — the custom_vmap XLA
    fallback only applies to vmapped lanes."""
    out = run_sub(
        """
        import json
        import numpy as np
        from repro.graphs.generators import erdos
        from repro.graphs.problems import matching_lp
        from repro.core.mwu import MWUOptions
        from repro.kernels import dispatch
        from repro.api import Solver
        from repro.dist import DistSolver, MeshPlan

        prob = matching_lp(erdos(60, 201, seed=1))
        solver = DistSolver(MWUOptions(kernel_backend="pallas"),
                            plan=MeshPlan(pod=2, data=1))
        before = dispatch.stats().get("gather", {}).get("pallas", 0)
        res = solver.feasible(prob, 7.0)
        after = dispatch.stats().get("gather", {}).get("pallas", 0)
        ref = Solver().feasible(prob, 7.0)
        print(json.dumps({
            "pallas_gather_delta": after - before,
            "status": int(res.status), "ref_status": int(ref.status),
            "max_px": float(res.max_px), "ref_max_px": float(ref.max_px),
        }))
        """,
        devices=2,
    )
    d = json.loads(out.strip().splitlines()[-1])
    assert d["pallas_gather_delta"] > 0, "Pallas pack fell back to XLA under shard_map"
    assert d["status"] == d["ref_status"]
    np.testing.assert_allclose(d["max_px"], d["ref_max_px"], rtol=5e-3, atol=5e-3)


def test_placed_graphs_launch_without_padding_or_transfer(tmp_path):
    """After ``DistSolver.place``, solves of two placed graphs in turn, and of
    a copy of one with another bracket, pad and transfer nothing and give
    what the same solves give without ``place``; each placement is one
    ``dist.place`` span in the profiler's trace, outside every solve."""
    out = run_sub(
        f"""
        import dataclasses, json
        from pathlib import Path
        import jax
        import numpy as np
        from jax.profiler import ProfileData
        from repro.api import MWUOptions
        from repro.dist import DistSolver, MeshPlan
        from repro.dist import solver as dist_solver
        from repro.graphs.generators import erdos
        from repro.graphs.problems import matching_lp

        probs = [matching_lp(erdos(60, 201, seed=1)), matching_lp(erdos(50, 150, seed=2))]

        def rebracket(p):
            return dataclasses.replace(p, lo=float(p.lo) * 0.9, hi=float(p.hi) * 1.1)

        def solve_all(solver, ps):
            sols = [solver.solve(p) for p in ps + ps[:1] + [rebracket(ps[1])]]
            return [[np.asarray(s.x).tolist(), s.bound, s.objective, s.feasibility_calls] for s in sols]

        opts = MWUOptions()
        plan = MeshPlan(pod=4, data=1)
        want = solve_all(DistSolver(opts, plan=plan), probs)
        solver = DistSolver(opts, plan=plan)
        jax.profiler.start_trace({str(tmp_path)!r})
        placed = [solver.place(p) for p in probs]
        calls = []
        pad, put = dist_solver.slab_pad_problem, jax.device_put
        dist_solver.slab_pad_problem = lambda *a: calls.append("pad") or pad(*a)
        jax.device_put = lambda *a, **k: calls.append("put") or put(*a, **k)
        got = solve_all(solver, placed)
        again = solver.place(placed[0]) is placed[0]
        jax.profiler.stop_trace()
        data = ProfileData.from_file(str(next(Path({str(tmp_path)!r}).rglob("*.xplane.pb"))))
        spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                 for plane in data.planes if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events
                 if ev.name in ("dist.place", "solver.solve")]
        u = placed[0].P.u
        print(json.dumps({{
            "want": want, "got": got, "calls": calls, "again": again, "spans": spans,
            "u_shards": sorted(s.data.shape[0] for s in u.addressable_shards),
        }}))
        """,
        devices=4,
    )
    d = json.loads(out.strip().splitlines()[-1])
    assert d["calls"] == [] and d["again"]
    assert d["u_shards"] == [51] * 4  # 201 edges padded to 204, a slab on each device
    places = [s for s in d["spans"] if s[0] == "dist.place"]
    solves = [s for s in d["spans"] if s[0] == "solver.solve"]
    assert len(places) == 2 and len(solves) == 4
    assert all(p[2] <= s[1] or s[2] <= p[1] for p in places for s in solves)
    for (x, bound, objective, calls), (wx, wbound, wobjective, wcalls) in zip(d["got"], d["want"]):
        assert len(x) == len(wx) and x == wx  # over the graph's own edges, bit for bit
        assert (bound, objective, calls) == (wbound, wobjective, wcalls)


def test_pod_collectives_lie_under_the_pod_psum_scope():
    """Every all-reduce of the pod-4 match program (psum, pmax, pmin) is under
    ``pod.psum``; the one-device program puts no op there."""
    out = run_sub(
        """
        import json
        from repro.dist import DistSolver, MeshPlan
        from repro.graphs.generators import erdos
        from repro.graphs.problems import matching_lp
        from repro.tracecheck.hlo_ir import parse_hlo
        from repro.utils.hlo import op_scopes

        prob = matching_lp(erdos(60, 201, seed=1))
        rows = {}
        for name, plan in (("pod4", MeshPlan(pod=4, data=1)), ("one", MeshPlan())):
            text = DistSolver(plan=plan).lower_batch(prob, [3.0, 7.0, 12.0, 20.0]).compile().as_text()
            reduces = [op.name for c in parse_hlo(text).comps.values() for op in c.ops
                       if op.kind.startswith("all-reduce")]
            rows[name] = {"reduces": reduces, "scoped": sorted(op_scopes(text, ("pod.psum",)))}
        print(json.dumps(rows))
        """,
        devices=4,
    )
    d = json.loads(out.strip().splitlines()[-1])
    assert d["pod4"]["reduces"] and set(d["pod4"]["reduces"]) <= set(d["pod4"]["scoped"])
    assert d["one"]["reduces"] == [] and d["one"]["scoped"] == []
