"""The control of each cell's check: the same run with the computation one
precision below what the configuration states, which `correct` must
refuse.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 5]

Each driver defines its control as `control(cell) -> checks`.  The fit
drivers switch on the program's own lower-precision path
(`EmbedSpec.kernel_precision = "bfloat16"`: the kernels' weights stored
in bfloat16) and run the cell at its size (`harness.program_control`).  A
driver for a program with no such path puts the reference in the
program's place, computed one precision lower.

Prints one line per seed with every number compared and its limit; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_run(cell) -> dict:
    """{name: (value, limit)} of the cell's check under its control: the
    cell's driver says what its control is (`control(cell)`)."""
    from bench import harness

    driver = harness.load_module(os.path.join(
        harness.BENCH, "drivers", cell.traffic["driver"] + ".py"))
    return driver.control(cell)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ["REPRO_AUTOTUNE_CACHE"] = harness.TILE_CACHE
    for seed in (int(s) for s in args.seeds.split(",")):
        cell, _ = harness.make_cell(args.workload, seed, args.seconds,
                                    trace=False)
        cell.t_start = time.perf_counter()
        harness.device_info(cell.chips)
        checks = control_run(cell)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "refused": any(v > lim for v, lim in
                                         checks.values()),
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in checks.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
