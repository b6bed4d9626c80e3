"""The dense single-device optimizer driver — now a thin wrapper over the
unified fit engine (embed/engine.py).

The whole iteration (direction -> backtracking line search -> update) stays
ONE jitted XLA program per (strategy, kind, line-search config, shapes):
`DenseObjective.make_fused_step` hands `_step` to the engine, whose Python
loop only does trace bookkeeping and convergence checks — so wall-clock
comparisons across strategies remain apples-to-apples (as in the paper's
figures, which plot E vs runtime and vs iterations), and results are
bit-identical to the pre-engine driver.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .affinities import Affinities
from .linesearch import LSConfig, backtracking
from .objectives import energy, energy_and_grad

Array = jnp.ndarray


@dataclasses.dataclass
class MinimizeResult:
    X: Array
    energies: np.ndarray      # E_k, k = 0..n_iters (includes E_0)
    grad_norms: np.ndarray
    step_sizes: np.ndarray
    times: np.ndarray         # cumulative wall-clock seconds at each iterate
    n_fevals: np.ndarray      # cumulative energy evaluations
    n_iters: int
    converged: bool
    setup_time: float         # strategy init (e.g. Cholesky factorization)
    strategy_state: Any = None


@functools.partial(
    jax.jit, static_argnames=("strategy", "kind", "ls_cfg", "impl")
)
def _step(strategy, kind, ls_cfg: LSConfig, X, E, G, state,
          alpha_prev, Wp, Wm, lam, impl=()):
    impl = dict(impl)   # hashable (k, v) pairs -> kernels.ops kwargs
    aff = Affinities(Wp, Wm)
    # device scopes (docs/observability.md): op metadata only
    with jax.named_scope("direction-solve"):
        P, state = strategy.direction(state, X, G, aff, kind, lam)
    if ls_cfg.init_step == "adaptive":
        alpha0 = alpha_prev
    elif ls_cfg.init_step == "adaptive_grow":
        alpha0 = jnp.minimum(alpha_prev / ls_cfg.rho, 1.0)
    else:
        alpha0 = jnp.ones_like(alpha_prev)
    if ls_cfg.max_rel_move is not None:
        xc = X - jnp.mean(X, axis=0, keepdims=True)
        scale = jnp.sqrt(jnp.mean(xc * xc)) + 1e-3
        p_rms = jnp.sqrt(jnp.mean(P * P)) + 1e-30
        alpha0 = jnp.minimum(alpha0, ls_cfg.max_rel_move * scale / p_rms)

    def energy_of(Xn):
        with jax.named_scope("objective"):
            return energy(Xn, aff, kind, lam, **impl)

    ls = backtracking(energy_of, X, E, G, P, alpha0, ls_cfg)
    X_new = X + ls.alpha * P
    with jax.named_scope("objective"):
        E_new, G_new = energy_and_grad(X_new, aff, kind, lam, **impl)
    n_evals = ls.n_evals + 1
    # everything the engine's host loop reads, packed so that it costs one
    # device->host transfer: [E, |G|, alpha, n_evals] (n_evals is exact)
    scalars = jnp.stack([E_new, jnp.linalg.norm(G_new),
                         ls.alpha.astype(E_new.dtype),
                         n_evals.astype(E_new.dtype)])
    return X_new, E_new, G_new, state, ls.alpha, n_evals, scalars


@dataclasses.dataclass
class DenseObjective:
    """Dense single-device backend of the engine's Objective protocol.

    Deterministic (key is ignored).  `make_fused_step` closes over the
    jitted `_step`, so the engine runs one XLA program per iteration.
    `X0` seeds the strategy state (some strategies size warm starts from
    it, e.g. SparseSD's prev_P).
    """

    aff: Affinities
    kind: str
    lam: Array
    strategy: Any
    ls_cfg: LSConfig
    X0: Array
    # kernels.ops dispatch kwargs as hashable (key, value) pairs — static
    # under `_step`'s jit (e.g. (("impl", "pallas"),
    # ("storage_dtype", "bfloat16")))
    impl: tuple = ()

    stochastic = False

    def energy_and_grad(self, X, key):
        return energy_and_grad(X, self.aff, self.kind, self.lam,
                               **dict(self.impl))

    def energy(self, X, key):
        return energy(X, self.aff, self.kind, self.lam, **dict(self.impl))

    def make_direction_solver(self):
        def solve(state, X, G):
            return self.strategy.direction(
                state, X, G, self.aff, self.kind, self.lam)

        # strategy.init may factor a Cholesky etc. — this is the setup cost
        state0 = self.strategy.init(self.X0, self.aff, self.kind, self.lam)
        return solve, state0

    def make_fused_step(self):
        def step(X, E, G, state, alpha_prev):
            return _step(self.strategy, self.kind, self.ls_cfg,
                         X, E, G, state, alpha_prev, self.aff.Wp,
                         self.aff.Wm, self.lam, impl=self.impl)

        return step


def minimize(
    X0: Array,
    aff: Affinities,
    kind: str,
    lam,
    strategy,
    max_iters: int = 500,
    tol: float = 1e-7,
    ls_cfg: LSConfig = LSConfig(),
    callback: Callable[[int, Array, float], None] | None = None,
    max_seconds: float | None = None,
) -> MinimizeResult:
    """DEPRECATED: use `repro.api.Embedding` (the dense backend runs this
    exact glue — trajectories are bit-identical).  Kept as a shim for
    legacy call sites."""
    import warnings

    warnings.warn(
        "core.minimize.minimize is deprecated; use repro.api.Embedding "
        "with backend='dense' (bit-identical trajectories)",
        DeprecationWarning, stacklevel=2)
    return _minimize(X0, aff, kind, lam, strategy, max_iters=max_iters,
                     tol=tol, ls_cfg=ls_cfg, callback=callback,
                     max_seconds=max_seconds)


def _minimize(
    X0: Array,
    aff: Affinities,
    kind: str,
    lam,
    strategy,
    max_iters: int = 500,
    tol: float = 1e-7,
    ls_cfg: LSConfig = LSConfig(),
    callback: Callable[[int, Array, float], None] | None = None,
    max_seconds: float | None = None,
) -> MinimizeResult:
    """Minimize E(X; lam) with the given search-direction strategy.

    Stops on relative energy decrease < tol, on max_iters, or (for the
    paper's fixed-budget comparisons) on max_seconds of wall-clock.
    """
    # deferred: repro.embed.engine <- repro.embed.__init__ <- trainer <-
    # repro.core would be circular at module-import time
    from repro.embed.engine import LoopConfig, fit_loop

    lam = jnp.asarray(lam, dtype=X0.dtype)
    obj = DenseObjective(aff, kind, lam, strategy, ls_cfg, X0)
    res = fit_loop(
        obj, X0,
        LoopConfig(max_iters=max_iters, tol=tol, ls=ls_cfg,
                   convergence="raw", max_seconds=max_seconds),
        callback=callback,
    )
    return MinimizeResult(
        X=res.X,
        energies=res.energies,
        grad_norms=res.grad_norms,
        step_sizes=res.step_sizes,
        times=res.times,
        n_fevals=res.n_fevals,
        n_iters=res.n_iters,
        converged=res.converged,
        setup_time=res.setup_time,
        strategy_state=res.state,
    )
