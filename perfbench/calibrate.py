"""Read the numbers that set a cell's limits: sound runs and the control.

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 [--seconds 0]

For each seed, one process-wide set-up of the cell's driver, one window
(``--seconds 0``: one pass of the pool), then every answer checked twice: as the program produced it
(the sound reading) and rounded to bfloat16 first (the control: the answer
a solver computing in the precision below the configuration's float32 would
hand over). Prints one JSON line per seed. The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from perfbench import harness  # noqa: E402
from perfbench import run as bench_run  # noqa: E402


def to_bfloat16(x):
    import jax.numpy as jnp
    import numpy as np

    return np.asarray(x, np.float32).astype(jnp.bfloat16).astype(np.float64)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    devices = bench_run.prepare(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        drv = harness.driver(cell).Driver(cell, seed, devices)
        drv.setup()
        window_s = drv.window(args.seconds)
        drv.free()
        sound = drv.check()
        control = drv.check(to_bfloat16)
        print(json.dumps({"workload": cell.name, "seed": seed, "window_s": window_s,
                          "sound": sound, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
