"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints name,...,derived CSV rows.  --quick (default) uses container-scale
Ns so the whole suite finishes on one CPU core; --full uses paper scale.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (fig1_learning_curves, fig2_random_inits,
                        fig3_homotopy, fig4_large, fig5_sparse_scaling,
                        kernel_bench, sd_overhead, serve_bench,
                        telemetry_smoke)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale Ns (hours on this container)")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal CI subset (~1 min): tiny fig1 + fig5")
    ap.add_argument("--bench-out", default="BENCH_smoke.json",
                    help="where --smoke writes the machine-readable bench "
                         "summary the CI regression gate compares against "
                         "the committed results/fig5.json baseline")
    a, _ = ap.parse_known_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs("results", exist_ok=True)
    print("table,fields...,derived")
    if a.smoke:
        fig1_learning_curves.run(n_per=16, loops=3, iters=10,
                                 out_json="results/fig1.json")
        # iters=12 -> 11 timed iterations per cell: the bench-regression
        # gate diffs these against the committed baseline, and 4-iteration
        # cells are too noise-dominated to gate on
        res5 = fig5_sparse_scaling.run(ns=(256, 1024), iters=12, k=10, m=5,
                                       perplexity=3.0, dense_cutoff=512,
                                       models=("ee", "tsne"),
                                       out_json="results/fig5.json")
        # instrumented sparse-SD fits: writes results/telemetry/{model}_sd/
        # run.jsonl + trace.json (uploaded as CI artifacts) and the solver
        # health + overhead numbers the regression gate checks
        res_tel = telemetry_smoke.run(n=2048, iters=12, perplexity=3.0,
                                      out_dir="results/telemetry")
        # kernel microbench: jnp vs fixed-tile vs autotuned Pallas + the
        # HBM cap-lift parity demo; the regression gate diffs its timings
        # against results/kernels.json and checks autotuned <= fixed
        res_k = kernel_bench.run(ns=(512, 1024), pairwise_ns=(256,),
                                 hbm_n=512, out_json="results/kernels.json")
        # serving path: artifact round-trip + concurrent transform server;
        # the gate checks max_abs_err/bit-exactness unconditionally and
        # diffs p50/p99 against the committed results/serve.json baseline
        res_srv = serve_bench.run(n=512, n_queries=48, iters=20,
                                  transform_iters=15,
                                  out_json="results/serve.json")
        import jax
        with open(a.bench_out, "w") as f:
            json.dump({"fig5": res5, "telemetry": res_tel,
                       "kernels": res_k, "serve": res_srv,
                       "meta": {"jax": jax.__version__,
                                "devices": len(jax.devices()),
                                "unix_time": time.time()}}, f)
        return
    if a.full:
        fig1_learning_curves.run(n_per=72, loops=10, iters=400,
                                 out_json="results/fig1.json")
        fig1_learning_curves.headline(n_per=72, loops=10, budget_s=420.0)
        fig2_random_inits.run(n_inits=50, budget_s=20.0,
                              out_json="results/fig2.json")
        fig3_homotopy.run(n_stages=50, max_iters=10_000,
                          out_json="results/fig3.json")
        fig4_large.run(n=20_000, budget_s=3600.0, kappa=7,
                       out_json="results/fig4.json")
        sd_overhead.run(ns=(1000, 5000, 20_000))
        fig5_sparse_scaling.run(ns=(2000, 10_000, 50_000), iters=10,
                                models=("ee", "tsne"),
                                out_json="results/fig5.json")
        kernel_bench.run(ns=(4096, 16_384), pairwise_ns=(1024,),
                         hbm_n=1024, out_json="results/kernels.json")
    else:
        fig1_learning_curves.run(n_per=36, loops=6, iters=60,
                                 out_json="results/fig1.json")
        # the paper's headline claim at COIL-720 scale (SD's 200-iter energy
        # vs GD/FP given a 120 s budget -> 'speedup > Nx' rows)
        fig1_learning_curves.headline(n_per=72, loops=10, budget_s=120.0)
        fig2_random_inits.run(n_inits=4, budget_s=2.0,
                              out_json="results/fig2.json")
        fig3_homotopy.run(n_stages=6, max_iters=150,
                          out_json="results/fig3.json")
        fig4_large.run(n=1200, budget_s=10.0,
                       out_json="results/fig4.json")
        sd_overhead.run(ns=(500, 1000))
        fig5_sparse_scaling.run(ns=(1000, 4000), iters=8,
                                dense_cutoff=2000, models=("ee", "tsne"),
                                out_json="results/fig5.json")
        kernel_bench.run(out_json="results/kernels.json")
        serve_bench.run(out_json="results/serve.json")
    # roofline table if a dry-run sweep exists
    if os.path.exists("results/dryrun.jsonl"):
        from benchmarks import roofline_report
        rows = roofline_report.load("results/dryrun.jsonl")
        print(f"roofline,rows,{len(rows)}")


if __name__ == "__main__":
    main()
