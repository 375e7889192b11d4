"""Run one cell of the benchmark once and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout.
Set-up (start-up, graphs, LPs, placement, loading or compiling every
program the window runs) is timed from the start of this script; then the
cell's driver measures for ``--seconds`` seconds; then every answer the
window produced is checked against the plain reference. With ``--trace 1``
the JAX profiler records the window's launches up to the first that starts
``TRACE_SECONDS`` into it, with each program's HLO, and the per-layer
metrics are read from that trace (on the planes of the chips the cell ran
on; device ops by the named scope their own program gives them) and from
the whole window's counters; with ``--trace 0`` the end-to-end metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``busy_s`` and
``window_s`` when traced), ``breakdown`` when traced, and last ``checks``,
each number compared beside its limit. Those also end standard error.
A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from perfbench import harness  # noqa: E402
from perfbench.trace import WINDOW_SPAN  # noqa: E402

WORK = ROOT / ".bench"  # compile cache and traces; never committed
TRACE_SECONDS = 3.0


def _finite(value):
    return value if isinstance(value, (int, str)) or math.isfinite(value) else None


def prepare(cell, rehearse: bool = False):
    """The devices the cell runs on, with the compile cache and precision set.

    ``rehearse`` skips the look for a chip and the compile cache (the CPU
    tests drive a run with it).
    """
    import jax

    devices = _devices(cell, rehearse)
    if not rehearse:
        WORK.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(WORK / "jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_enable_x64", cell.config["precision"] == "float64")
    return devices


def _devices(cell, rehearse: bool):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise harness.BenchmarkError(f"JAX found no devices: {e}") from e
    if not rehearse and devices[0].platform != "tpu":
        raise harness.BenchmarkError(f"needs a TPU, found {devices[0].platform!r}; no CPU fallback")
    if len(devices) < cell.chips:
        raise harness.BenchmarkError(f"cell {cell.name} needs {cell.chips} chips, found {len(devices)}")
    return devices[: cell.chips]


def _memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run_cell(cell, seed: int, seconds: float, traced: bool, rehearse: bool = False, answer=None) -> dict:
    """Set up, measure, check; the result object of one run.

    ``rehearse`` as for ``prepare``; ``answer`` maps each x before it is
    checked.
    """
    import jax

    devices = prepare(cell, rehearse)

    drv = harness.driver(cell).Driver(cell, seed, devices)
    drv.setup()
    run = harness.RunRecord(cell, devices[0].device_kind, len(devices))
    run.setup_s = time.perf_counter() - T0

    # the profiler records the window's launches up to the first to start
    # TRACE_SECONDS into it: a whole window of small ops would make a trace
    # of gigabytes and take minutes to write and read
    trace_dir = WORK / "trace" / cell.name
    tracing: list = []  # the open span while the profiler records

    def stop_tracing():
        if tracing:
            tracing.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()
            run.traced = drv.counter.recorded

    def before_launch():
        if time.perf_counter() - t_window >= TRACE_SECONDS:
            stop_tracing()

    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the bench.* spans are host TraceMe events
        options.enable_hlo_proto = True  # each program's HLO, to look its ops' scopes up in
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        span = jax.profiler.TraceAnnotation(WINDOW_SPAN)  # made after the start, or it records nothing
        span.__enter__()
        tracing.append(span)
        drv.counter.before_launch = before_launch
    t_window = time.perf_counter()
    try:
        run.window_s = drv.window(seconds)
    finally:
        stop_tracing()
    drv.record(run)
    peak = _memory_peak(devices)
    drv.free()

    checks, attempted, failed = drv.check() if answer is None else drv.check(answer)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": harness.verdict(checks, attempted, failed), "attempted": attempted, "failed": failed}
    if traced:
        from perfbench import trace

        run.trace = trace.summarize(trace.load(trace_dir), {d.id for d in devices})
        shutil.rmtree(trace_dir, ignore_errors=True)
        if run.trace is not None:
            device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
            if run.trace.unmapped:
                print(f"perfbench: no HLO in the trace for {', '.join(run.trace.unmapped)}: "
                      "no op is read by scope", file=sys.stderr)
            if run.trace.unplaced_s:
                print(f"perfbench: {run.trace.unplaced_s} s of device ops ran in no program: "
                      "read by opnames/", file=sys.stderr)
    result["metrics"] = harness.read_metrics(run, cell.per_layer if traced else cell.end_to_end)
    result["device"] = device
    if traced and run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]} for k, c in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.resolve(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except harness.BenchmarkError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    for line in harness.check_lines(result["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
