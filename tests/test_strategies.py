"""Partial-Hessian strategies: descent property, limits, convergence order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.core import (
    DiagH, FP, GD, SD, SDMinus, LBFGS, NonlinearCG,
    LSConfig, energy_and_grad, make_affinities,
    laplacian_eigenmaps, make_strategy,
)
from tests.conftest import dense_fit, three_loops

ALL_STRATEGIES = [GD(), FP(), DiagH(), SD(), SDMinus(), LBFGS(m=10), NonlinearCG()]


@pytest.fixture(scope="module")
def problem():
    Y = three_loops(n_per=20, loops=2, dim=8)
    aff = make_affinities(Y, 10.0, model="ee")
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1
    return aff, X0


@pytest.mark.parametrize("strat", ALL_STRATEGIES, ids=lambda s: s.name)
def test_descent_direction(problem, strat):
    """p^T g < 0 — the property that makes Thm 2.1 apply (B_k pd)."""
    aff, X0 = problem
    lam = 20.0
    state = strat.init(X0, aff, "ee", lam)
    X = X0
    for it in range(3):
        _, G = energy_and_grad(X, aff, "ee", lam)
        P, state = strat.direction(state, X, G, aff, "ee", lam)
        assert float(jnp.vdot(P, G)) < 0.0, f"{strat.name} iter {it}"
        X = X + 0.01 * P / (jnp.linalg.norm(P) + 1e-30)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 500), kind=st.sampled_from(["ee", "ssne", "tsne"]))
def test_sd_descent_property(seed, kind):
    Y = three_loops(n_per=10, loops=2, dim=6, seed=seed % 4)
    aff = make_affinities(Y, 5.0, model=kind)
    X = jax.random.normal(jax.random.PRNGKey(seed), (Y.shape[0], 2))
    lam = 1.0 if kind in ("ssne", "tsne") else 10.0
    strat = SD()
    state = strat.init(X, aff, kind, lam)
    _, G = energy_and_grad(X, aff, kind, lam)
    P, _ = strat.direction(state, X, G, aff, kind, lam)
    assert float(jnp.vdot(P, G)) < 0.0


def test_sd_solves_linear_system(problem):
    """SD direction satisfies B p = -g to fp32-refined accuracy."""
    aff, X0 = problem
    strat = SD()
    state = strat.init(X0, aff, "ee", 20.0)
    _, G = energy_and_grad(X0, aff, "ee", 20.0)
    P, _ = strat.direction(state, X0, G, aff, "ee", 20.0)
    resid = state["B"] @ P + G
    rel = float(jnp.linalg.norm(resid) / jnp.linalg.norm(G))
    assert rel < 5e-2


def test_sd_kappa_zero_equals_fp(problem):
    """The paper's family endpoints: SD(kappa=0) == FP up to the jitter."""
    aff, X0 = problem
    lam = 20.0
    _, G = energy_and_grad(X0, aff, "ee", lam)
    sd0 = SD(kappa=0)
    fp = FP()
    p_sd, _ = sd0.direction(sd0.init(X0, aff, "ee", lam), X0, G, aff, "ee", lam)
    p_fp, _ = fp.direction(fp.init(X0, aff, "ee", lam), X0, G, aff, "ee", lam)
    rel = float(jnp.linalg.norm(p_sd - p_fp) / jnp.linalg.norm(p_fp))
    assert rel < 1e-3


def test_sd_beats_gd_in_fixed_iterations(problem):
    """The paper's headline: SD descends far deeper per iteration budget."""
    aff, X0 = problem
    lam = 100.0
    r_gd = dense_fit(X0, aff, "ee", lam, GD(), max_iters=40, tol=0.0)
    r_sd = dense_fit(X0, aff, "ee", lam, SD(), max_iters=40, tol=0.0,
                     ls_cfg=LSConfig(init_step="adaptive_grow"))
    assert r_sd.energies[-1] < r_gd.energies[-1]


def test_make_strategy():
    assert isinstance(make_strategy("sd", kappa=5), SD)
    assert isinstance(make_strategy("sd-"), SDMinus)
    with pytest.raises(ValueError):
        make_strategy("bogus")


def test_monotone_decrease(problem):
    aff, X0 = problem
    for strat in (SD(), SDMinus(), LBFGS(m=5)):
        res = dense_fit(X0, aff, "ee", 50.0, strat, max_iters=25, tol=0.0)
        e = res.energies
        assert np.all(np.diff(e) <= 1e-3 * np.maximum(np.abs(e[:-1]), 1.0)), strat.name
