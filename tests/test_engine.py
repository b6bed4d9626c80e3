"""Unified fit engine (embed/engine.py): bit-identity of the refactored
core.minimize and of the fused loop's packed fetch, the loop's transfer
count, and checkpoint/resume reproducibility through the engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Embedding, EmbedSpec
from repro.core import (FP, GD, SD, LSConfig, energy_and_grad,
                        laplacian_eigenmaps, make_affinities, minimize)
from repro.core.minimize import DenseObjective, _step
from repro.embed import DistributedEmbedding, EmbedConfig
from repro.embed.engine import LoopConfig, fit_loop
from tests.conftest import three_loops


@pytest.fixture(scope="module")
def problem():
    Y = three_loops(n_per=16, loops=2, dim=8)
    aff = make_affinities(Y, 8.0, model="ee")
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1
    return Y, aff, X0


def _seed_minimize(X0, aff, kind, lam, strategy, max_iters, tol, ls_cfg):
    """The pre-engine core.minimize driver loop, pinned verbatim (minus
    timing): the engine's fused-step path must reproduce it bit-for-bit."""
    lam = jnp.asarray(lam, dtype=X0.dtype)
    state = jax.block_until_ready(strategy.init(X0, aff, kind, lam))
    E, G = jax.block_until_ready(energy_and_grad(X0, aff, kind, lam))
    X = X0
    alpha = jnp.asarray(1.0, dtype=X0.dtype)
    energies = [float(E)]
    gnorms = [float(jnp.linalg.norm(G))]
    steps: list[float] = []
    fevals = [1]
    converged = False
    it = 0
    for it in range(1, max_iters + 1):
        X, E_new, G, state, alpha, ne, _ = jax.block_until_ready(
            _step(strategy, kind, ls_cfg, X, E, G, state, alpha,
                  aff.Wp, aff.Wm, lam))
        energies.append(float(E_new))
        gnorms.append(float(jnp.linalg.norm(G)))
        steps.append(float(alpha))
        fevals.append(fevals[-1] + int(ne))
        rel = abs(energies[-2] - energies[-1]) / max(abs(energies[-1]), 1e-30)
        if rel < tol:
            converged = True
            break
        E = E_new
    return X, energies, gnorms, steps, fevals, it, converged


@pytest.mark.parametrize("strategy,ls_cfg", [
    (SD(), LSConfig(init_step="adaptive_grow")),
    (SD(), LSConfig(init_step="adaptive")),
    (GD(), LSConfig()),
])
def test_minimize_bit_identical_to_seed_driver(problem, strategy, ls_cfg):
    _, aff, X0 = problem
    X, energies, gnorms, steps, fevals, n_iters, converged = _seed_minimize(
        X0, aff, "ee", 50.0, strategy, 20, 1e-6, ls_cfg)
    res = minimize(X0, aff, "ee", 50.0, strategy, max_iters=20, tol=1e-6,
                   ls_cfg=ls_cfg)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(res.X))
    assert energies == list(res.energies)
    assert gnorms == list(res.grad_norms)
    assert steps == list(res.step_sizes)
    assert fevals == list(res.n_fevals)
    assert n_iters == res.n_iters
    assert converged == res.converged


@pytest.mark.parametrize("sparse,kind,lam", [
    (False, "ee", 50.0),
    (True, "ee", 50.0),
    # normalized kind: the checkpoint payload additionally carries the
    # ratio estimator's streaming partition-function state (carry_state/
    # restore_carry), without which the post-resume gradients diverge
    (True, "tsne", 1.0),
], ids=["dense-mesh", "sparse", "sparse-normalized"])
def test_resume_replays_uninterrupted_trace(tmp_path, sparse, kind, lam):
    """Interrupted-vs-uninterrupted runs produce IDENTICAL energy traces:
    the checkpoint payload carries the line-search and solver state (plus
    objective carry state where it exists), and (on the sparse path) the
    per-iteration fold_in keys make the surrogate exactly reproducible."""
    Y = three_loops(n_per=16, loops=2, dim=8)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    base = dict(kind=kind, lam=lam, perplexity=8.0, tol=0.0, sparse=sparse,
                n_neighbors=24 if sparse else 0, n_negatives=8)

    full = DistributedEmbedding(
        EmbedConfig(max_iters=12, **base), mesh).fit(Y)

    ckdir = str(tmp_path / "ck")
    DistributedEmbedding(
        EmbedConfig(max_iters=6, checkpoint_dir=ckdir,
                    checkpoint_every=100, **base), mesh).fit(Y)
    res = DistributedEmbedding(
        EmbedConfig(max_iters=12, checkpoint_dir=ckdir,
                    checkpoint_every=100, **base), mesh).fit(Y)

    assert res.resumed_from == 6
    assert res.n_iters == 6
    # E at the restored iterate equals the uninterrupted run's E there (the
    # sparse path re-evaluates it through the grad-enabled program, whose
    # XLA reduction fusion differs slightly from the line-search fast path)
    np.testing.assert_allclose(res.energies[0], full.energies[6], rtol=1e-3)
    # every post-resume iterate replays the uninterrupted trajectory exactly
    np.testing.assert_array_equal(res.energies[1:], full.energies[7:13])
    np.testing.assert_array_equal(np.asarray(res.X), np.asarray(full.X))


def test_engine_max_seconds_and_traces(problem):
    """EngineResult trace invariants surface through minimize()."""
    _, aff, X0 = problem
    res = minimize(X0, aff, "ee", 50.0, SD(), max_iters=15, tol=0.0)
    assert len(res.energies) == res.n_iters + 1
    assert len(res.step_sizes) == res.n_iters
    assert res.n_fevals[-1] >= res.n_iters
    assert np.all(np.isfinite(res.energies))


def _unpacked_fused_loop(obj, X0, max_iters, tol):
    """The fused branch of the engine loop before its scalars were packed,
    pinned: block on the step, an eager |G|, four transfers.  Its step is
    the fused step less the packed vector, which XLA drops as dead code."""
    aff = obj.aff
    unpacked = jax.jit(lambda *a: _step(obj.strategy, obj.kind, obj.ls_cfg,
                                        *a)[:6])

    def step(X, E, G, state, alpha):
        return unpacked(X, E, G, state, alpha, aff.Wp, aff.Wm, obj.lam)

    _, state = obj.make_direction_solver()
    E, G = jax.block_until_ready(obj.energy_and_grad(X0, None))
    e0, g0 = jax.device_get((E, jnp.linalg.norm(G)))
    energies, gnorms, steps, fevals = [float(e0)], [float(g0)], [], [1]
    X, alpha = X0, jnp.asarray(1.0, dtype=X0.dtype)
    it = 0
    for it in range(1, max_iters + 1):
        X, E_new, G, state, alpha, ne = jax.block_until_ready(
            step(X, E, G, state, alpha))
        vals = jax.device_get((E_new, jnp.linalg.norm(G), alpha, ne))
        energies.append(float(vals[0]))
        gnorms.append(float(vals[1]))
        steps.append(float(vals[2]))
        fevals.append(fevals[-1] + int(vals[3]))
        rel = abs(energies[-2] - energies[-1]) / max(abs(energies[-1]), 1e-30)
        if rel < tol:
            break
        E = E_new
    return X, energies, gnorms, steps, fevals, it


@pytest.mark.parametrize("strategy", [SD(), FP()], ids=["sd", "fp"])
def test_packed_fetch_keeps_the_fused_iterates(problem, strategy):
    """The fused loop reads each iteration's scalars in one packed
    transfer; the iterates are bit for bit those of the unpacked loop, and
    |G|, now reduced inside the step, agrees to float32 round-off."""
    _, aff, X0 = problem
    ls = LSConfig(init_step="adaptive_grow")
    obj = DenseObjective(aff, "ee", jnp.asarray(50.0, X0.dtype), strategy,
                         ls, X0)
    X, energies, gnorms, steps, fevals, n_iters = _unpacked_fused_loop(
        obj, X0, 20, 1e-6)
    res = fit_loop(obj, X0, LoopConfig(max_iters=20, tol=1e-6, ls=ls,
                                       convergence="raw"))
    np.testing.assert_array_equal(np.asarray(X), np.asarray(res.X))
    assert energies == list(res.energies)
    assert steps == list(res.step_sizes)
    assert fevals == list(res.n_fevals)
    assert n_iters == res.n_iters
    np.testing.assert_allclose(res.grad_norms, gnorms, rtol=1e-6, atol=0)
    assert res.loop_transfers == res.n_iters


@pytest.mark.parametrize("backend", ["dense", "sparse", "tree"])
def test_loop_transfers_count_the_host_fetches(backend):
    """`loop_transfers` (EngineResult and the recorder's meta): one packed
    fetch per iteration on the fused path; on the host path each gradient
    fetch is two arrays, each line-search energy one, <G, P> one and the
    trust cap's two scalars two: n_evals + 4 an iteration."""
    Y = three_loops(n_per=16, loops=2, dim=8)
    spec = EmbedSpec(kind="ee", lam=50.0, strategy="sd", backend=backend,
                     perplexity=4.0, n_neighbors=8, max_iters=5, tol=0.0)
    emb = Embedding(spec).fit(Y, telemetry=True)
    res = emb.result_
    if backend == "dense":
        expected = res.n_iters
    else:
        expected = int(res.n_fevals[-1] - res.n_fevals[0]) + 4 * res.n_iters
    assert res.loop_transfers == expected
    assert emb.telemetry_.recorder.meta["loop_transfers"] == expected
