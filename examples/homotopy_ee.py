"""Homotopy optimization demo (paper §3.1, Fig. 3): follow the minimum path
X(lambda) from the convex spectral regime to the target lambda, comparing
the spectral direction against the fixed-point iteration.

    PYTHONPATH=src python examples/homotopy_ee.py --stages 8
"""
import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import FP, SD, LSConfig, laplacian_eigenmaps, make_affinities
from repro.embed import homotopy_path
from repro.data import coil_like


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=8)
    ap.add_argument("--lam", type=float, default=100.0)
    ap.add_argument("--n-per", type=int, default=36)
    ap.add_argument("--loops", type=int, default=6)
    a = ap.parse_args()

    Y = jnp.asarray(coil_like(n_per=a.n_per, loops=a.loops, dim=64))
    aff = make_affinities(Y, perplexity=12.0, model="ee")
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1

    for name, strat, ls in [("SD", SD(), "adaptive_grow"),
                            ("FP", FP(), "one")]:
        h = homotopy_path(X0, aff, "ee", strat, lam_final=a.lam,
                          n_stages=a.stages, tol=1e-6, max_iters=400,
                          ls_cfg=LSConfig(init_step=ls))
        print(f"{name}: total iters {int(h.iters_per_lambda.sum()):5d}  "
              f"fevals {int(h.fevals_per_lambda.sum()):5d}  "
              f"time {h.time_per_lambda.sum():6.2f}s  "
              f"final E {h.energies[-1]:.4f}")
        per = ", ".join(
            f"lam={l:.2g}:{int(i)}" for l, i in
            zip(h.lambdas, h.iters_per_lambda))
        print(f"  iters per lambda: {per}")


if __name__ == "__main__":
    main()
