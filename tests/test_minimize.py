"""Dense-fit behaviour through the engine: line search, convergence
accounting, homotopy."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SD, GD, LSConfig, energy, energy_and_grad, laplacian_eigenmaps,
    make_affinities,
)
from repro.core.linesearch import backtracking
from repro.embed import homotopy_path
from tests.conftest import dense_fit, three_loops


@pytest.fixture(scope="module")
def problem():
    Y = three_loops(n_per=16, loops=2, dim=8)
    aff = make_affinities(Y, 8.0, model="ee")
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1
    return aff, X0


def test_backtracking_satisfies_armijo(problem):
    aff, X0 = problem
    lam = 50.0
    E0, G = energy_and_grad(X0, aff, "ee", lam)
    P = -G
    cfg = LSConfig()
    res = backtracking(lambda X: energy(X, aff, "ee", lam), X0, E0, G, P,
                       jnp.asarray(1.0), cfg)
    assert bool(res.success)
    gtp = float(jnp.vdot(G, P))
    assert float(res.e_new) <= float(E0) + cfg.c1 * float(res.alpha) * gtp


def test_minimize_traces_consistent(problem):
    aff, X0 = problem
    res = dense_fit(X0, aff, "ee", 50.0, SD(), max_iters=15, tol=0.0)
    assert len(res.energies) == res.n_iters + 1
    assert len(res.times) == res.n_iters + 1
    assert res.n_fevals[-1] >= res.n_iters  # at least one eval per iteration
    assert np.all(np.isfinite(res.energies))
    assert res.setup_time >= 0.0


def test_minimize_tol_stops_early(problem):
    aff, X0 = problem
    res = dense_fit(X0, aff, "ee", 50.0, SD(), max_iters=500, tol=1e-6,
                    ls_cfg=LSConfig(init_step="adaptive_grow"))
    assert res.converged
    assert res.n_iters < 500


def test_max_seconds_budget(problem):
    aff, X0 = problem
    res = dense_fit(X0, aff, "ee", 50.0, GD(), max_iters=100_000, tol=0.0,
                    max_seconds=1.0)
    assert res.times[-1] < 20.0  # generous: one step + compile


def test_homotopy_runs_and_descends(problem):
    aff, X0 = problem
    hres = homotopy_path(X0, aff, "ee", SD(), lam_final=50.0, n_stages=4,
                         tol=1e-4, max_iters=60)
    assert hres.X.shape == X0.shape
    assert np.all(np.isfinite(hres.energies))
    # the final embedding at the target lambda should beat the initial X0
    e_direct0 = float(energy(X0, aff, "ee", 50.0))
    assert hres.energies[-1] < e_direct0
