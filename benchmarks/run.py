"""Benchmark entry point: one section per paper table/figure.

  table2  bench_solvers      MWU vs exact LP vs specialized algos
  table3  bench_stepsize     std / binary / newton step rules
  fig3    bench_convergence  MWU vs MPCSolver iteration counts
  fig5    bench_breakdown    component split + implicit-vs-explicit
  fig4    bench_scaling      DistSolver pod/data scaling vs device count
                             (writes BENCH_dist.json at the repo root)
  roofline bench_roofline    dry-run roofline table (§Roofline source)
  serving bench_serving      lpserve continuous batching vs sequential
  kernels bench_kernels      pallas kernel pack vs XLA, per op + solve
                             (writes BENCH_kernels.json at the repo root)
  tracecheck repro.tracecheck static jaxpr/HLO lint of the benched entry
                             points — the same family x backend x plan
                             matrix the CI gate sweeps (writes
                             TRACECHECK.json at the repo root)

``python -m benchmarks.run [section ...] [--quick]`` — default: all.
``--quick`` shrinks the kernels and fig4 sections to CI-smoke sizes. The solver
benches enable x64 (paper runs in f64 on CPU; DESIGN.md §7).
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ALL_SECTIONS = [
    "table2", "table3", "fig3", "fig5", "fig4", "roofline", "serving", "kernels",
    "tracecheck",
]


def main() -> None:
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    jax.config.update("jax_enable_x64", True)
    enable_compile_cache()

    argv = sys.argv[1:]
    quick = "--quick" in argv
    sections = [a for a in argv if not a.startswith("--")] or ALL_SECTIONS
    t00 = time.perf_counter()
    for s in sections:
        print(f"\n===== {s} =====", flush=True)
        t0 = time.perf_counter()
        if s == "table2":
            from . import bench_solvers

            bench_solvers.run(small=True)
        elif s == "table3":
            from . import bench_stepsize

            bench_stepsize.run(scale=12)
        elif s == "fig3":
            from . import bench_convergence

            bench_convergence.run()
        elif s == "fig5":
            from . import bench_breakdown

            bench_breakdown.run(scale=14)
        elif s == "fig4":
            from . import bench_scaling

            records = bench_scaling.run(quick=quick)
            out = Path(__file__).resolve().parents[1] / "BENCH_dist.json"
            out.write_text(json.dumps(records, indent=2) + "\n")
            print(f"wrote {out}", flush=True)
        elif s == "roofline":
            from . import bench_roofline

            bench_roofline.run()
        elif s == "serving":
            from . import bench_serving

            bench_serving.run()
        elif s == "kernels":
            from . import bench_kernels, bench_roofline

            records = bench_kernels.run(quick=quick)
            bench_roofline.run_kernels(records=records["per_op"])
            out = Path(__file__).resolve().parents[1] / "BENCH_kernels.json"
            out.write_text(json.dumps(records, indent=2) + "\n")
            print(f"wrote {out}", flush=True)
        elif s == "tracecheck":
            # the bench driver lints exactly the matrix the CI gate
            # sweeps (repro.tracecheck.matrix.default_matrix) — the
            # benched configurations and the linted ones cannot drift.
            from repro.tracecheck.cli import run_matrix

            root = Path(__file__).resolve().parents[1]
            out = root / "TRACECHECK.json"
            cm_out = root / "COSTMODEL.json"
            report = run_matrix(quick=quick, out=str(out), costmodel_out=str(cm_out))
            print(f"wrote {out} and {cm_out}", flush=True)
            if not report["ok"]:
                sys.exit(1)
        else:
            print(f"unknown section {s}")
        print(f"[{s}: {time.perf_counter()-t0:.1f}s]", flush=True)
    print(f"\n[total: {time.perf_counter()-t00:.1f}s]", flush=True)


if __name__ == "__main__":
    main()
