"""CPU rehearsal of every cell at a tiny size, through the same drivers.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

Each cell's configuration is shrunk (rows, input width, neighbours,
perplexity, iteration caps; widths as the chip runs them are untouched in
the files), the run goes through `bench.harness.run_cell` with the chip
and dispatch checks off, and the checks are printed.  It never prints a
metric line: a CPU run gives no device number."""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHRINK = {"n": 1000, "dim": 32, "n_per": 24, "loops": 3}
SPEC_SHRINK = {"perplexity": 8.0, "n_neighbors": 24, "cg_maxiter": 20}


def tiny(cell) -> None:
    data = cell.config["data"]
    for k, v in SHRINK.items():
        if k in data:
            data[k] = min(data[k], v)
    n = data.get("n", data.get("n_per", 0) * data.get("loops", 0))
    cell.config["n_points"], cell.config["input_dim"] = n, data["dim"]
    spec = cell.config["spec"]
    for k, v in SPEC_SHRINK.items():
        if k in spec:
            spec[k] = min(spec[k], v)
    spec["kernel_impl"] = "pallas-interpret"
    t = cell.traffic
    if "max_iters" in t:
        t["max_iters"] = min(int(t["max_iters"]), 5)
    if "e_init" in t:            # reached at the first iteration
        t["e_init"] = 1e30
    if "rate" in t:
        t["rate"] = min(float(t["rate"]), 20.0)


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    if jax.devices()[0].platform != "cpu":
        print("rehearse: run with JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    from bench import harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    names = (argv if argv else [w["name"] for w in bench["workloads"]])
    for name in names:
        for trace in (False, True):
            cell, _ = harness.make_cell(name, seed=2 ** 31 + 7, seconds=2.0,
                                        trace=trace, rehearsal=True)
            tiny(cell)
            cell.t_start = time.perf_counter()
            res = harness.run_cell(cell, bench)
            print(f"rehearse {name} trace={int(trace)}: correct="
                  f"{res['correct']} attempted={res['attempted']} "
                  f"failed={res['failed']} metrics={sorted(res['metrics'])}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
