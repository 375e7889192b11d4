"""Host-clock cost of one batched MWU iteration and of its pieces, on Graph500.

    PYTHONPATH=src python benchmarks/iter_probe.py [--scales 16,18]
        [--families match,vcover] [--what ops,iter,probe]

For each Graph500 scale (edgefactor 16, seed 1) and family, four lanes
vmapped in f32 under the XLA policy, every time ending in
``block_until_ready`` and taken after a warm-up call:

* ``ops``   — the vmapped ``matvec`` and ``rmatvec`` of the family's
  incidence operator (the scatter-add and the gather);
* ``iter``  — ``Solver.solve_batch`` on four bounds between the builder's
  ``lo`` and ``hi``, capped at two ``max_iter`` values: the difference in
  seconds over the difference in the slowest lane's iterations is the
  cost of one batched iteration;
* ``probe`` — one line-search probe (``make_probe_fn`` with the Newton
  slopes) on a random state of the family's constraint widths, timed the
  same way from two probe counts.

Each line also carries the device's ``peak_bytes_in_use`` so far. One run
each, no spread: these are readings of where the time goes, not a
benchmark. Only ``make_probe_fn``, ``Solver.solve_batch``, ``build`` and
``kron`` are used, so the script also times other checkouts of the solver
put first on ``PYTHONPATH``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import MWUOptions, Solver
from repro.core.stepsize import make_probe_fn
from repro.graphs import build, kron

LANES = 4
EPS = 0.1


def timed(fn):
    """(output, seconds) of ``fn()`` after one warm-up call."""
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def per_unit(fn, counts) -> tuple[float, float]:
    """(seconds per unit, first-call seconds) of ``fn(count)`` between two counts."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(counts[0]))
    first = time.perf_counter() - t0
    (n_a, t_a), (n_b, t_b) = ((*timed(lambda c=c: fn(c)),) for c in counts)
    return (t_b - t_a) / max(int(n_b) - int(n_a), 1), first


def ops_facts(problem) -> dict:
    op = problem.P if problem.P is not None else problem.C
    xs = jnp.ones((LANES, op.shape[1]), jnp.float32)
    ys = jnp.ones((LANES, op.shape[0]), jnp.float32)
    matvec, rmatvec = jax.jit(jax.vmap(op.matvec)), jax.jit(jax.vmap(op.rmatvec))
    _, mv = timed(lambda: matvec(xs))
    _, rv = timed(lambda: rmatvec(ys))
    return {"matvec4_s": mv, "rmatvec4_s": rv}


def iter_facts(problem, counts=(3, 13)) -> dict:
    bounds = list(np.linspace(float(problem.lo), float(problem.hi), LANES + 2)[1:-1])

    def run(k):
        res = Solver(MWUOptions(eps=EPS, max_iter=k, kernel_backend="xla")).solve_batch(problem, bounds)
        return jnp.max(res.iters), res

    def iters(k):
        it, res = run(k)
        jax.block_until_ready(res)
        return it

    per_it, first = per_unit(iters, counts)
    return {"per_batched_iter_s": per_it, "iter_first_call_s": first}


def probe_facts(problem, counts=(2, 18)) -> dict:
    P, C, _, _ = problem.instantiate(float(problem.hi))
    rows_y = P.shape[0] if P is not None else 1
    rows_z = C.shape[0] if C is not None else 1
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.uniform(0.5, 1.0, (LANES, rows_y)), jnp.float32)
    z = jnp.asarray(rng.uniform(0.2, 0.9, (LANES, rows_z)), jnp.float32)
    dy = jnp.asarray(rng.uniform(0.0, 1e-6, (LANES, rows_y)), jnp.float32)
    dz = jnp.asarray(rng.uniform(0.0, 1e-6, (LANES, rows_z)), jnp.float32)
    eta = float(np.log(2 * max(rows_y, rows_z)) / EPS)

    @jax.jit
    def sweep(k, y, z, dy, dz):
        def one(y, z, dy, dz):
            probe = make_probe_fn(y, z, dy, dz, eta, with_grad=True)
            return jax.lax.fori_loop(
                0, k, lambda i, acc: acc + probe(1.0 + 0.01 * i).f, jnp.zeros((), y.dtype)
            )

        return jax.vmap(one)(y, z, dy, dz)

    def probes(k):
        jax.block_until_ready(sweep(k, y, z, dy, dz))
        return k

    per_probe, _ = per_unit(probes, counts)
    return {"rows_y": rows_y, "rows_z": rows_z, "per_probe_s": per_probe}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scales", default="16,18")
    ap.add_argument("--families", default="match,vcover")
    ap.add_argument("--what", default="ops,iter,probe")
    args = ap.parse_args(argv)
    what = set(args.what.split(","))
    dev = jax.devices()[0]
    print(f"device platform={dev.platform} kind={dev.device_kind}", flush=True)
    for scale in (int(s) for s in args.scales.split(",")):
        t0 = time.perf_counter()
        g = kron(scale, seed=1, edgefactor=16)
        graph_s = time.perf_counter() - t0
        for family in args.families.split(","):
            problem = build(family, g)
            facts = {"scale": scale, "family": family, "edges": g.m, "graph_s": graph_s}
            if "ops" in what:
                facts.update(ops_facts(problem))
            if "iter" in what:
                facts.update(iter_facts(problem))
            if "probe" in what:
                facts.update(probe_facts(problem))
            facts["peak_bytes"] = (dev.memory_stats() or {}).get("peak_bytes_in_use")
            print(" ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


if __name__ == "__main__":
    main()
