"""Each cell's check catches the faults its timed path can have.

A tiny copy of each cell (bench/rehearse.py's shrink) runs through the
harness with the chip check off and the program broken underneath, and
`correct` must come out false:

- a step that returns its state unchanged;
- half of the work left out (half the rows in the energy);
- an answer altered where it is produced.

One chip per cell, so no exchange between chips can be left out.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import importlib
import time

import numpy as np
import pytest

from bench import harness, rehearse

FIT = "coil20-dense-ee-fit"
ITER = "mnist20k-sparse-tsne-iter"


def run(name, target_iters=None, seconds=1.0):
    import jax

    jax.clear_caches()          # a patched function must be traced anew
    cell, bench = harness.make_cell(name, seed=2 ** 31 + 11, seconds=seconds,
                                    trace=False, rehearsal=True)
    rehearse.tiny(cell)
    if target_iters is not None:
        cell.traffic["e_init"], cell.traffic["target_ratio"] = target_iters
        cell.traffic["max_iters"] = 40
    cell.t_start = time.perf_counter()
    return harness.run_cell(cell, bench)


@pytest.fixture(scope="module")
def fit_target():
    """A target the tiny coil fit reaches in about ten iterations."""
    from repro.api import Embedding

    from bench import data

    cell, _ = harness.make_cell(FIT, seed=2 ** 31 + 11, seconds=1.0,
                                trace=False, rehearsal=True)
    rehearse.tiny(cell)
    Y, _ = data.rows(cell.config["data"], cell.seed)
    spec = harness.program_spec(cell, max_iters=10, tol=0.0)
    E = Embedding(spec).fit(Y).result_.energies
    return float(E[0]), float(E[10] / E[0]) * 1.0001


def test_sound_runs_are_correct(fit_target):
    assert run(FIT, target_iters=fit_target)["correct"]
    assert run(ITER)["correct"]


def _unchanged_dense_step(monkeypatch):
    minimize = importlib.import_module("repro.core.minimize")
    real = minimize._step

    def step(strategy, kind, ls_cfg, X, E, G, state, alpha, *a, **kw):
        out = real(strategy, kind, ls_cfg, X, E, G, state, alpha, *a, **kw)
        return (X, E, G) + tuple(out[3:])

    monkeypatch.setattr(minimize, "_step", step)


def _half_dense_energy(monkeypatch):
    from repro.kernels import ops

    real = ops.pairwise_terms

    def half(X, Wa, Wb, kind, **kw):
        keep = (np.arange(X.shape[0]) < X.shape[0] // 2)[:, None]
        return real(X, Wa * keep, Wb * keep, kind, **kw)

    monkeypatch.setattr(ops, "pairwise_terms", half)


def _altered_dense_answer(monkeypatch):
    minimize = importlib.import_module("repro.core.minimize")
    real = minimize._step

    def step(*a, **kw):
        out = real(*a, **kw)
        return (out[0] * 1.01,) + tuple(out[1:])

    monkeypatch.setattr(minimize, "_step", step)


@pytest.mark.parametrize("fault", [_unchanged_dense_step, _half_dense_energy,
                                   _altered_dense_answer])
def test_fit_cell_fails_on_fault(fault, monkeypatch, fit_target):
    fault(monkeypatch)
    assert not run(FIT, target_iters=fit_target)["correct"]


def _unchanged_sparse_step(monkeypatch):
    from repro.embed import trainer

    monkeypatch.setattr(trainer._SparseObjective, "make_direction_solver",
                        lambda self: (lambda st, X, G: (0.0 * G, st),
                                      jnp_zeros(self._X0)))


def jnp_zeros(X):
    import jax.numpy as jnp

    return jnp.zeros_like(X)


def _half_sparse_energy(monkeypatch):
    from repro.embed import trainer

    real = trainer.energy_and_grad_sparse

    def half(X, saff, *a, **kw):
        g = saff.graph
        keep = (np.arange(g.n) < g.n // 2)[:, None]
        saff = saff._replace(graph=g._replace(weights=g.weights * keep))
        return real(X, saff, *a, **kw)

    monkeypatch.setattr(trainer, "energy_and_grad_sparse", half)


def _altered_sparse_answer(monkeypatch):
    from repro.embed import engine

    real = engine.host_backtrack

    def altered(*a, **kw):
        alpha, e_new, n = real(*a, **kw)
        return 1.01 * alpha, e_new, n

    monkeypatch.setattr(engine, "host_backtrack", altered)


@pytest.mark.parametrize("fault", [_unchanged_sparse_step,
                                   _half_sparse_energy,
                                   _altered_sparse_answer])
def test_iter_cell_fails_on_fault(fault, monkeypatch):
    fault(monkeypatch)
    assert not run(ITER)["correct"]
