"""Homotopy optimization over lambda (paper §3.1, Fig. 3).

Start near lambda = 0 where E is convex (dominated by the spectral E+) and
follow the minimum path X(lambda) to the target lambda, warm-starting each
stage from the previous solution.  Slower than direct minimization but finds
deeper minima (Carreira-Perpinan 2010).  Works with every strategy; the SD
Cholesky factor does not depend on lambda and is reused across all stages.
Each stage is one dense fit through the engine (`DenseObjective` +
`fit_loop`, raw relative-decrease convergence).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.affinities import Affinities
from repro.core.linesearch import LSConfig
from repro.core.minimize import DenseObjective

from .engine import EngineResult, LoopConfig, fit_loop

Array = jnp.ndarray


@dataclasses.dataclass
class HomotopyResult:
    X: Array
    lambdas: np.ndarray
    energies: np.ndarray          # final E at each lambda
    iters_per_lambda: np.ndarray
    fevals_per_lambda: np.ndarray
    time_per_lambda: np.ndarray
    results: list[EngineResult]


def homotopy_path(
    X0: Array,
    aff: Affinities,
    kind: str,
    strategy,
    lam_final: float,
    n_stages: int = 50,
    lam_start: float = 1e-4,
    tol: float = 1e-6,
    max_iters: int = 10_000,
    ls_cfg: LSConfig = LSConfig(),
) -> HomotopyResult:
    """Paper settings: 50 log-spaced lambdas from 1e-4 to the target, inner
    tolerance 1e-6 relative decrease or 1e4 iterations."""
    lambdas = np.logspace(
        np.log10(lam_start), np.log10(lam_final), n_stages
    )
    loop_cfg = LoopConfig(max_iters=max_iters, tol=tol, ls=ls_cfg,
                          convergence="raw")
    X = X0
    results: list[EngineResult] = []
    for lam in lambdas:
        obj = DenseObjective(aff, kind, jnp.asarray(lam, X0.dtype), strategy,
                             ls_cfg, X)
        res = fit_loop(obj, X, loop_cfg)
        X = res.X
        results.append(res)
    return HomotopyResult(
        X=X,
        lambdas=lambdas,
        energies=np.asarray([r.energies[-1] for r in results]),
        iters_per_lambda=np.asarray([r.n_iters for r in results]),
        fevals_per_lambda=np.asarray([r.n_fevals[-1] for r in results]),
        time_per_lambda=np.asarray([r.times[-1] + r.setup_time for r in results]),
        results=results,
    )
