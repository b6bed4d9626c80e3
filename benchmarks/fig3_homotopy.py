"""Paper Fig. 3: homotopy optimization of EE over a log-spaced lambda path;
iterations / runtime / function evaluations per lambda, per method."""
from __future__ import annotations

import argparse
import json


from repro.core import LSConfig
from repro.embed import homotopy_path

from .common import coil_problem, csv_row, method_by_name


def run(methods=("SD", "FP", "L-BFGS"), n_stages=10, lam_final=100.0,
        tol=1e-6, max_iters=300, out_json=None):
    _, aff, X0 = coil_problem(model="ee")
    results = {}
    for name in methods:
        strat, ls = method_by_name(name)
        h = homotopy_path(X0, aff, "ee", strat, lam_final=lam_final,
                          n_stages=n_stages, tol=tol, max_iters=max_iters,
                          ls_cfg=LSConfig(init_step=ls))
        csv_row("fig3", name, int(h.iters_per_lambda.sum()),
                int(h.fevals_per_lambda.sum()),
                f"{h.time_per_lambda.sum():.2f}",
                f"{h.energies[-1]:.6g}")
        results[name] = {
            "lambdas": h.lambdas.tolist(),
            "iters": h.iters_per_lambda.tolist(),
            "fevals": h.fevals_per_lambda.tolist(),
            "time": h.time_per_lambda.tolist(),
            "final_E": float(h.energies[-1]),
        }
    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=10)
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    run(n_stages=a.stages, max_iters=a.iters, out_json=a.out)


if __name__ == "__main__":
    main()
