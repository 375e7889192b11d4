"""Figure 4 / Table 4 analogue: distributed-MWU scaling on repro.dist.

Strong scaling of the mesh-sharded :class:`repro.dist.DistSolver` over
the first N of ``jax.devices()``, every N in this one process (a chip
belongs to one process, so no child may need it). On CPU, fabricate the
devices before starting: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
Counts the host does not have are reported as skipped. Each count solves
the same problem two ways:

* ``pod=N``  edge-slab matching feasibility — the paper's MPI edge
  partition: each device owns E/N incidence rows, psum is the neighbor
  exchange. Reports MWU iteration throughput (iters/s, wall).
* ``data=N`` batched bound fan-out — N binary-search probes solved as
  one shard_map launch, one lane per device. Reports lane throughput
  (lane-iters/s).

Fabricated devices share one CPU, so wall-clock *speedup* is not
expected there; what the numbers certify is that per-device work shrinks
with pod (iters/s should not collapse as N grows) and that the data axis
fans out at near-constant cost per lane. A failed count raises, so the
run exits non-zero.

``run()`` prints the CSV and returns the records dict that
``benchmarks/run.py`` serializes to ``BENCH_dist.json``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.mwu import MWUOptions
from repro.dist import DistSolver, MeshPlan
from repro.graphs.generators import rgg
from repro.graphs.problems import matching_lp

from .common import Csv


def _timed(fn):
    """(result, seconds) of the second call; the first compiles."""
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _count(ndev: int, prob, opts: MWUOptions) -> dict:
    rec = {"devices": ndev}
    # pod=N: edge-slab sharded feasibility (the paper's partition scheme)
    solver = DistSolver(opts, plan=MeshPlan(pod=ndev, data=1))
    r, dt = _timed(lambda: solver.feasible(prob, prob.lo))
    it = int(np.asarray(r.iters))
    rec["pod"] = {"iters": it, "seconds": dt, "iters_per_s": it / max(dt, 1e-9),
                  "status": int(np.asarray(r.status)),
                  "psum_rounds": solver.dist_stats["psum_rounds"]}
    # data=N: one probe per device, a full binary-search fan-out in 1 launch
    bounds = list(np.linspace(prob.lo, prob.hi, ndev))
    solver = DistSolver(opts, plan=MeshPlan(pod=1, data=ndev))
    res, dt = _timed(lambda: solver.solve_batch(prob, bounds))
    lane_it = int(np.asarray(res.iters).sum())
    rec["data"] = {"lanes": ndev, "lane_iters": lane_it, "seconds": dt,
                   "lane_iters_per_s": lane_it / max(dt, 1e-9),
                   "feasible_lanes": int(np.asarray(res.feasible).sum())}
    return rec


def run(quick: bool = False):
    """Benchmark DistSolver across device counts; returns the records dict."""
    counts = (1, 2, 4) if quick else (1, 2, 4, 8)
    scale = 10 if quick else 12
    max_iter = 300 if quick else 2000
    devices = jax.devices()
    g = rgg(scale, seed=7)
    prob = matching_lp(g)
    opts = MWUOptions(eps=0.1, max_iter=max_iter)
    csv = Csv(
        "devices,pod_iters_per_s,pod_psum_rounds,data_lane_iters_per_s,data_feasible_lanes"
    )
    records = {"bench": "dist_scaling", "quick": quick, "scale": scale,
               "max_iter": max_iter, "n_vertices": g.n, "n_edges": g.m,
               "platform": devices[0].platform, "device_kind": devices[0].device_kind,
               "per_devices": [], "skipped_devices": []}
    for ndev in counts:
        if ndev > len(devices):
            records["skipped_devices"].append(ndev)
            print(f"skip {ndev} devices: only {len(devices)} visible", flush=True)
            continue
        d = _count(ndev, prob, opts)
        records["per_devices"].append(d)
        csv.add(ndev, f"{d['pod']['iters_per_s']:.1f}", d["pod"]["psum_rounds"],
                f"{d['data']['lane_iters_per_s']:.1f}", d["data"]["feasible_lanes"])
    csv.dump()
    return records
