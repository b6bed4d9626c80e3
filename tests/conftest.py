"""Shared fixtures. NOTE: no XLA device-count forcing here — smoke tests and
benchmarks must see the real single CPU device; only launch/dryrun.py forces
512 host devices (and runs as its own process).

Also the trace-contract pytest plugin (docs/analysis.md): thin fixture
wrappers over `repro.analysis.guards` so any test can pin XLA compile
counts or wrap a hot loop in jax's transfer/leak guards without
importing the package machinery."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import guards as _guards
from repro.core.linesearch import LSConfig
from repro.core.minimize import DenseObjective
from repro.embed.engine import LoopConfig, fit_loop


@pytest.fixture
def assert_compile_count():
    """`with assert_compile_count(expected=0): ...` — fail on retraces.
    Warm the exact call sequence up first (eager ops also compile)."""
    return _guards.assert_compile_count


@pytest.fixture
def compile_counter():
    """Context manager counting XLA backend compiles in a block."""
    return _guards.CompileCounter


@pytest.fixture
def no_implicit_transfers():
    """transfer_guard("disallow") context: implicit host->device
    transfers inside the block raise."""
    return _guards.no_implicit_transfers


@pytest.fixture
def no_tracer_leaks():
    """jax.checking_leaks() context: escaped tracers raise."""
    return _guards.no_tracer_leaks


def three_loops(n_per: int = 40, loops: int = 3, dim: int = 16, seed: int = 0):
    """COIL-like synthetic data: `loops` 1-D closed manifolds in R^dim."""
    ts = jnp.linspace(0, 2 * jnp.pi, n_per, endpoint=False)
    pts = []
    for i in range(loops):
        c = jax.random.normal(jax.random.PRNGKey(seed + 10 + i), (dim,)) * 3
        proj = jax.random.normal(jax.random.PRNGKey(seed + 20 + i), (2, dim))
        circ = jnp.stack([jnp.cos(ts), jnp.sin(ts)], -1) @ proj
        pts.append(circ + c)
    return jnp.concatenate(pts)


def dense_fit(X0, aff, kind, lam, strategy, max_iters=500, tol=1e-7,
              ls_cfg=LSConfig(), max_seconds=None):
    """One dense fit with a strategy OBJECT (core/strategies): the
    `DenseObjective` through the engine with the raw relative-decrease
    test — what `repro.api`'s dense backend runs for a registered name."""
    obj = DenseObjective(aff, kind, jnp.asarray(lam, dtype=X0.dtype),
                         strategy, ls_cfg, X0)
    return fit_loop(obj, X0,
                    LoopConfig(max_iters=max_iters, tol=tol, ls=ls_cfg,
                               convergence="raw", max_seconds=max_seconds))


@pytest.fixture(scope="session")
def small_data():
    return three_loops(n_per=24, loops=3, dim=10)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
