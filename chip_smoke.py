"""Drive the MWU graph-LP solver's main path on one TPU chip and check it.

    python chip_smoke.py [--scale N]       # one chip
    python chip_smoke.py --four-chips      # DistSolver over four chips

Phases, each printed as one ``phase=... key=value ...`` line:

* ``device``  — the first device must be a TPU; there is no CPU fallback.
* ``small``   — rgg-11 x {match, vcover, dom-set, dense-sub} through
  ``Solver(MWUOptions(eps=0.1))``, each within the (1+eps) band of the
  exact LP (scipy HiGHS on the host).
* ``real``    — Graph500 (edgefactor 16, seed 1) match at scale 16 and
  vcover at scale 12, or both at ``--scale``, through ``Solver.solve``
  (batch_width 4): set-up, first-call and warm seconds, iteration
  counts, status, peak device bytes, and the certificate rechecked in
  numpy f64 on the host. These are the largest scales measured to fit
  the script's 20 minutes on one v5e: match's first solve at scale 18
  had not finished after 24 minutes there, and vcover's bound search
  takes several times match's iterations (ROADMAP §1.4).
* ``serving`` — mixed-size requests through ``LPEngine.solve_many``, which
  must match sequential ``Solver.solve``.
* ``dispatch`` — the kernel-dispatch counters; no op may have run a Pallas
  kernel in interpret mode.

``--four-chips`` runs only ``DistSolver(plan=MeshPlan(pod=4))`` match on
Graph500 (scale 16, or ``--scale``; the problem is built in host memory
and the solver puts one edge slab on each chip) against the one-chip
``Solver`` on the same graph, and prints each chip's peak bytes.

Everything runs in f32 (x64 stays off). Any failed check exits non-zero.
The ``done`` line gives the script's seconds from the device check on.
The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EPS = 0.1
TOL = 1e-3  # certificate slack for f32 solves, checked in f64
# Graph500 scale per family of the real-size phase (see the docstring)
REAL_SCALES = {"match": 16, "vcover": 12}
FOUR_CHIP_SCALE = 16


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **facts) -> None:
    print(f"phase={phase} " + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def solver_opts():
    from repro.api import MWUOptions

    return MWUOptions(eps=EPS)


def timed_solve(solver, problem):
    """(Solution, seconds) of one ``solve``, ending when the device is done."""
    import jax

    t0 = time.perf_counter()
    sol = solver.solve(problem)
    jax.block_until_ready(sol.last_result)
    return sol, time.perf_counter() - t0


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- certificates, recomputed on the host in f64 ----------------------------
def match_certificate(g, x, greedy: float) -> dict:
    """x >= 0, every vertex load <= 1 + TOL, objective >= greedy/(1+eps)."""
    x = np.asarray(x, np.float64)
    load = np.bincount(g.u, x, minlength=g.n) + np.bincount(g.v, x, minlength=g.n)
    cert = {"min_x": x.min(), "max_load": load.max(), "objective": x.sum()}
    check(cert["min_x"] >= 0.0, f"match: negative x {cert['min_x']}")
    check(cert["max_load"] <= 1.0 + TOL, f"match: vertex load {cert['max_load']} > 1+{TOL}")
    check(
        cert["objective"] >= greedy / (1.0 + EPS),
        f"match: objective {cert['objective']} < greedy {greedy} / (1+eps)",
    )
    return cert


def vcover_certificate(g, x, greedy: float) -> dict:
    """Every edge covered to 1 - TOL, objective <= (1+eps) * 2 * greedy."""
    x = np.asarray(x, np.float64)
    cover = x[g.u] + x[g.v]
    cert = {"min_x": x.min(), "min_cover": cover.min(), "objective": x.sum()}
    check(cert["min_x"] >= 0.0, f"vcover: negative x {cert['min_x']}")
    check(cert["min_cover"] >= 1.0 - TOL, f"vcover: edge covered to {cert['min_cover']} < 1-{TOL}")
    check(
        cert["objective"] <= (1.0 + EPS) * 2.0 * greedy,
        f"vcover: objective {cert['objective']} > (1+eps) * 2 * greedy {greedy}",
    )
    return cert


def in_band(value: float, exact: float, sense: str) -> bool:
    """The (1+eps) band around the exact LP value, with f32 slack TOL.

    A max problem certifies a value in [exact/(1+eps), exact]; a min
    problem one in [exact, (1+eps) exact]. Densest subgraph certifies a
    density bound D whose packing rows hold to (1+eps), so D may sit
    below rho* by that factor as well.
    """
    lo, hi = exact / (1.0 + EPS), exact * (1.0 + EPS)
    if sense == "max":
        hi = exact
    elif sense == "min":
        lo = exact
    return lo * (1.0 - TOL) <= value <= hi * (1.0 + TOL)


# -- phases ---------------------------------------------------------------
def phase_small(scale: int = 11) -> None:
    """Every family on rgg-``scale`` against the exact LP (HiGHS)."""
    from repro.api import Solver
    from repro.graphs import baselines, build, rgg

    g = rgg(scale, seed=0)
    solver = Solver(solver_opts())
    for family, sense in (("match", "max"), ("vcover", "min"), ("dom-set", "min"), ("dense-sub", "")):
        sol, seconds = timed_solve(solver, build(family, g))
        exact, _ = baselines.exact_lp(family, g)
        value = sol.bound if family == "dense-sub" else sol.objective
        rel = abs(value - exact) / max(exact, 1e-12)
        report(
            "small", graph=g.name, family=family, status=sol.status, mwu=value, exact=exact,
            rel=rel, iters=sol.mwu_iters_total, probes=sol.ls_probes_total,
            calls=sol.feasibility_calls, seconds=seconds,
        )
        check(sol.feasible, f"small {family}: status {sol.status}, not feasible")
        check(in_band(value, exact, sense), f"small {family}: {value} outside the band of {exact}")


def phase_real(scales: dict[str, int], device) -> None:
    """Graph500 match and vcover, each at its scale, on one chip."""
    from repro.api import Solver
    from repro.graphs import build, kron

    solver = Solver(solver_opts(), batch_width=4)
    graphs = {}
    for family, certificate in (("match", match_certificate), ("vcover", vcover_certificate)):
        scale = scales[family]
        if scale not in graphs:
            t0 = time.perf_counter()
            g = graphs[scale] = kron(scale, seed=1, edgefactor=16)
            report("real", graph=g.name, vertices=g.n, edges=g.m,
                   graph_setup_s=time.perf_counter() - t0)
        g = graphs[scale]
        t0 = time.perf_counter()
        problem = build(family, g)
        setup_s = time.perf_counter() - t0
        # both builders bound the search with the greedy maximal matching:
        # match's lo is it, vcover's hi is twice it
        greedy = problem.lo if family == "match" else problem.hi / 2.0
        sol, first_s = timed_solve(solver, problem)
        sol, warm_s = timed_solve(solver, problem)
        check(sol.feasible, f"real {family}: status {sol.status}, not feasible")
        cert = certificate(g, sol.x, greedy)
        report(
            "real", graph=g.name, family=family, setup_s=setup_s, first_call_s=first_s,
            warm_s=warm_s, iters=sol.mwu_iters_total, probes=sol.ls_probes_total,
            calls=sol.feasibility_calls, status=sol.status, objective=sol.objective,
            greedy=greedy, peak_bytes=peak_bytes(device),
            **{f"cert_{k}": v for k, v in cert.items()},
        )


def phase_serving(requests: int = 6, lanes: int = 4) -> None:
    """Mixed-size traffic through the engine matches sequential solves."""
    from repro.api import Solver
    from repro.graphs import build, erdos
    from repro.lpserve import LPEngine, LPServeConfig

    tiers = [(40, 100), (60, 160), (80, 220)]
    families = ["match", "vcover"]
    probs = [
        build(families[i % 2], erdos(*tiers[i % len(tiers)], seed=i)) for i in range(requests)
    ]
    opts = solver_opts()
    engine = LPEngine(LPServeConfig(opts=opts, lanes=lanes))
    t0 = time.perf_counter()
    sols = engine.solve_many(probs)
    engine_s = time.perf_counter() - t0
    sequential = Solver(opts, batch_width=1)
    refs = [sequential.solve(p) for p in probs]
    stats = engine.stats()
    report(
        "serving", requests=requests, lanes=lanes, engine_s=engine_s, batches=stats["batches"],
        probes=stats["feasibility_calls"], compiles=stats["compiles"],
    )
    for i, (sol, ref) in enumerate(zip(sols, refs)):
        check(sol.feasible, f"serving request {i}: not feasible")
        rel = abs(sol.objective - ref.objective) / max(abs(ref.objective), 1e-12)
        check(rel <= 3.0 * EPS, f"serving request {i}: {sol.objective} vs sequential {ref.objective}")
    check(stats["batches"] < stats["feasibility_calls"], "serving: batching never kicked in")


def phase_dispatch() -> None:
    """No op on this path ran a Pallas kernel in interpret mode."""
    from repro.kernels import dispatch

    policy = dispatch.resolve(solver_opts().kernel_backend)
    stats = dispatch.stats()
    report("dispatch", backend=policy.backend, interpret=policy.interpret, stats=json.dumps(stats))
    check(not policy.interpret, "dispatch: the resolved policy runs Pallas in interpret mode")
    if policy.backend != "pallas":
        ran = {op: d["pallas"] for op, d in stats.items() if d["pallas"]}
        check(not ran, f"dispatch: pallas kernels ran under an xla policy: {ran}")


def _on_host():
    """Build arrays in host memory where the CPU backend is available."""
    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def phase_four_chips(scale: int) -> None:
    """Edge-slab DistSolver match on four chips vs the one-chip Solver."""
    import jax

    from repro.api import Solver
    from repro.dist import DistSolver, MeshPlan
    from repro.graphs import build, kron

    devices = jax.devices()
    check(len(devices) >= 4, f"four-chips: {len(devices)} devices visible, need 4")
    t0 = time.perf_counter()
    g = kron(scale, seed=1, edgefactor=16)
    dist = DistSolver(solver_opts(), plan=MeshPlan(pod=4), batch_width=4)
    with _on_host():
        problem = build("match", g)
    greedy = problem.lo
    report("four_chips", graph=g.name, vertices=g.n, edges=g.m, setup_s=time.perf_counter() - t0)

    sol4, first_s = timed_solve(dist, problem)
    sol4, warm_s = timed_solve(dist, problem)
    peaks = {str(d.id): peak_bytes(d) for d in devices[:4]}
    report(
        "four_chips", plan="pod4", mode="edge_slab", first_call_s=first_s, warm_s=warm_s,
        iters=sol4.mwu_iters_total, calls=sol4.feasibility_calls, status=sol4.status,
        objective=sol4.objective, peak_bytes_per_device=json.dumps(peaks),
    )
    check(sol4.feasible, f"four-chips: status {sol4.status}, not feasible")
    cert4 = match_certificate(g, sol4.x, greedy)

    sol1, one_s = timed_solve(Solver(solver_opts(), batch_width=4), jax.device_put(problem, devices[0]))
    report(
        "four_chips", plan="one_chip", first_call_s=one_s, iters=sol1.mwu_iters_total,
        calls=sol1.feasibility_calls, status=sol1.status, objective=sol1.objective,
    )
    check(sol4.status == sol1.status, f"four-chips: status {sol4.status} vs one chip {sol1.status}")
    check(
        sol1.objective / (1.0 + EPS) <= sol4.objective <= sol1.objective * (1.0 + EPS),
        f"four-chips: objective {sol4.objective} outside the band of one chip's {sol1.objective}",
    )
    report("four_chips", **{f"cert_{k}": v for k, v in cert4.items()})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help="Graph500 scale of every real-size family "
                         f"(default {REAL_SCALES}; {FOUR_CHIP_SCALE} with --four-chips)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip DistSolver phase and its one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}; no CPU fallback", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    t0 = time.perf_counter()
    report("device", platform=dev.platform, kind=dev.device_kind, count=len(devices),
           compile_cache=enable_compile_cache(), x64=jax.config.jax_enable_x64)
    try:
        if args.four_chips:
            phase_four_chips(args.scale or FOUR_CHIP_SCALE)
        else:
            phase_small()
            scales = {f: args.scale or s for f, s in REAL_SCALES.items()}
            phase_real(scales, dev)
            phase_serving()
            phase_dispatch()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    report("done", total_s=time.perf_counter() - t0)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
