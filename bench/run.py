"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and its
traffic are found by name (bench/harness.py).  Set-up (imports, data,
graph, warm-up and compilation) is timed from the start of this script
to the start of the measured window.  The run fails with a non-zero exit
and prints no result when JAX finds no TPU or fewer chips than the cell
needs, or when the checkout lacks the program.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("bench: --seed must be >= 0", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program (src/repro) in {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    # the program keeps its compile cache, and its kernels' tile picks,
    # where these variables say: inside the checkout, at fixed paths
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ["REPRO_AUTOTUNE_CACHE"] = harness.TILE_CACHE
    cell, bench = harness.make_cell(args.workload, args.seed, args.seconds,
                                    bool(args.trace))
    cell.t_start = T_START
    try:
        result = harness.run_cell(cell, bench)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
