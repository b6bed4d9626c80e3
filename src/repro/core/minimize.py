"""The dense single-device objective of the unified fit engine
(embed/engine.py).

The whole iteration (direction -> backtracking line search -> update) is
ONE jitted XLA program per (strategy, kind, line-search config, shapes):
`DenseObjective.make_fused_step` hands `_step` to the engine, whose Python
loop only does trace bookkeeping and convergence checks — so wall-clock
comparisons across strategies stay apples-to-apples (as in the paper's
figures, which plot E vs runtime and vs iterations).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from .affinities import Affinities
from .linesearch import LSConfig, backtracking
from .objectives import energy, energy_and_grad

Array = jnp.ndarray


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
