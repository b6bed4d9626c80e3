"""Theory-level checks tying the implementation to the paper's analysis."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (SD, LSConfig, energy, energy_and_grad,
                        make_affinities)
from repro.configs import RunConfig, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.data import batch_for
from repro.models import build_model, init_train_state, make_train_step
from repro.optim.adamw import AdamWConfig
from tests.conftest import dense_fit, three_loops


def test_sd_is_newton_at_lambda_zero():
    """At lambda=0 the objective is the spectral quadratic E+ whose Hessian
    IS the SD matrix (paper §2: 'it would achieve quadratic convergence in
    that case') — one unit SD step must essentially minimize E."""
    Y = three_loops(n_per=16, loops=2, dim=8)
    aff = make_affinities(Y, 8.0, model="ee")
    X0 = jax.random.normal(jax.random.PRNGKey(0), (Y.shape[0], 2)) * 2.0
    lam = jnp.asarray(0.0)
    strat = SD(mu_scale=1e-7)
    state = strat.init(X0, aff, "ee", lam)
    E0, G = energy_and_grad(X0, aff, "ee", lam)
    P, _ = strat.direction(state, X0, G, aff, "ee", lam)
    E1 = energy(X0 + P, aff, "ee", lam)
    assert float(E1) < 1e-3 * float(E0), (float(E0), float(E1))


def test_locally_linear_rate_improves_with_better_B():
    """Paper: rate r = ||B^-1 H - I||; more Hessian info => faster local
    convergence.  Near a minimum, SD contracts the gradient faster per
    iteration than FP."""
    from repro.core import FP
    Y = three_loops(n_per=14, loops=2, dim=8)
    aff = make_affinities(Y, 7.0, model="ee")
    lam = 20.0
    # get near a minimum first
    X0 = jax.random.normal(jax.random.PRNGKey(1), (Y.shape[0], 2)) * 0.5
    res = dense_fit(X0, aff, "ee", lam, SD(), max_iters=150, tol=1e-10,
                    ls_cfg=LSConfig(init_step="adaptive_grow"))
    Xstar_ish = res.X

    def contraction(strat, ls):
        r = dense_fit(Xstar_ish, aff, "ee", lam, strat, max_iters=6, tol=0.0,
                      ls_cfg=LSConfig(init_step=ls))
        g = r.grad_norms
        ratios = g[1:] / np.maximum(g[:-1], 1e-30)
        return float(np.median(ratios))

    c_sd = contraction(SD(), "adaptive_grow")
    c_fp = contraction(FP(), "one")
    assert c_sd < c_fp + 0.05, (c_sd, c_fp)


def test_grad_compression_preserves_training():
    """int8 error-feedback compression must not change the loss trajectory
    materially over a short run (ablation for DESIGN.md §5)."""
    cfg = get_smoke_config("qwen2-7b")
    shape = ShapeConfig("t", "train", 16, 4)

    def train(compress):
        run = RunConfig(num_microbatches=2, remat="none",
                        grad_compress=compress)
        model = build_model(cfg, run)
        state, _ = init_train_state(model, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(
            model, AdamWConfig(warmup_steps=2, total_steps=12)))
        losses = []
        for s in range(8):
            state, m = step(state, batch_for(cfg, shape, step=s))
            losses.append(float(m["loss"]))
        return losses

    base = train(False)
    comp = train(True)
    assert base[-1] < base[0]
    assert comp[-1] < comp[0]
    assert abs(comp[-1] - base[-1]) / base[-1] < 0.05, (base[-1], comp[-1])


def test_extension_kinds_minimize():
    """The paper's 'previously unexplored algorithms' (t-EE, Epanechnikov
    EE) train with SD out of the box."""
    Y = three_loops(n_per=12, loops=2, dim=8)
    for kind in ("tee", "epan"):
        aff = make_affinities(Y, 6.0, model=kind)
        X0 = jax.random.normal(jax.random.PRNGKey(2), (Y.shape[0], 2)) * 0.3
        res = dense_fit(X0, aff, kind, 10.0, SD(), max_iters=40, tol=0.0,
                        ls_cfg=LSConfig(init_step="adaptive_grow"))
        assert res.energies[-1] < res.energies[0]
        assert np.all(np.isfinite(res.energies)), kind
