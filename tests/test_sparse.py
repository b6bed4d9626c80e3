"""Sparse neighbor-graph subsystem: ELL invariants, dense<->sparse parity,
and CG-vs-Cholesky spectral-direction agreement (docs/sparse.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SD, energy_and_grad, energy_and_grad_sparse,
                        make_affinities, make_strategy)
from repro.core.laplacian import laplacian_matmul
from repro.core.strategies import SparseSD
from repro.kernels.ref import pairwise_terms_ref
from repro.sparse import (NeighborGraph, SparseAffinities, from_dense,
                          knn_cross, knn_graph, pcg, reverse_graph,
                          sparse_affinities, sparse_laplacian_eigenmaps,
                          sym_degree, sym_lap_matvec, to_dense)
from tests.conftest import three_loops

UNNORM = [("ee", 50.0), ("tee", 10.0), ("epan", 5.0)]
NORM = [("ssne", 5.0), ("tsne", 2.0)]


def _problem(n=41, d_hi=6, seed=0):
    Y = jax.random.normal(jax.random.PRNGKey(seed), (n, d_hi))
    X = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, 2)) * 0.5
    return Y, X


# -- graph construction ---------------------------------------------------------


def test_knn_exact_matches_brute_force():
    Y, _ = _problem(n=33)
    d2, idx = knn_graph(Y, 5, method="exact", block_rows=8)
    D2 = np.array(jnp.sum((Y[:, None] - Y[None]) ** 2, axis=-1))
    np.fill_diagonal(D2, np.inf)
    for i in range(Y.shape[0]):
        want = set(np.argsort(D2[i])[:5])
        assert set(np.asarray(idx[i])) == want, i


def test_knn_approx_high_recall_on_manifold_data():
    Y = three_loops(n_per=40, loops=2, dim=8)
    _, ie = knn_graph(Y, 5, method="exact")
    _, ia = knn_graph(Y, 5, method="approx", n_projections=8, window=12)
    hits = sum(len(set(np.asarray(ie[i])) & set(np.asarray(ia[i])))
               for i in range(Y.shape[0]))
    assert hits / (Y.shape[0] * 5) > 0.9


def test_ell_padding_invariant_exact_zero():
    """Padded slots (self index, zero weight) contribute exactly zero to
    every operator — bitwise, not approximately."""
    n, k = 16, 4
    key = jax.random.PRNGKey(0)
    idx = jax.random.randint(key, (n, k), 0, n, dtype=jnp.int32)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (n, k)))
    X = jax.random.normal(jax.random.PRNGKey(2), (n, 2))
    g = NeighborGraph(indices=idx, weights=w)
    # pad every row with extra self-edge zero-weight slots
    pad_idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, 3))
    gp = NeighborGraph(
        indices=jnp.concatenate([idx, pad_idx], axis=1),
        weights=jnp.concatenate([w, jnp.zeros((n, 3))], axis=1))
    np.testing.assert_array_equal(np.asarray(sym_lap_matvec(g, X)),
                                  np.asarray(sym_lap_matvec(gp, X)))
    np.testing.assert_array_equal(np.asarray(sym_degree(g)),
                                  np.asarray(sym_degree(gp)))
    np.testing.assert_array_equal(np.asarray(to_dense(g)),
                                  np.asarray(to_dense(gp)))


def test_from_dense_to_dense_roundtrip():
    Y, _ = _problem(n=20)
    aff = make_affinities(Y, 6.0, model="ee")
    g = from_dense(aff.Wp, k=aff.Wp.shape[0] - 1)
    np.testing.assert_allclose(np.asarray(to_dense(g)), np.asarray(aff.Wp),
                               rtol=1e-6, atol=1e-9)


def test_sym_lap_matvec_matches_dense_laplacian():
    Y, X = _problem()
    n = Y.shape[0]
    saff = sparse_affinities(Y, k=n - 1, perplexity=8.0, model="ee")
    aff = make_affinities(Y, 8.0, model="ee")
    got = sym_lap_matvec(saff.graph, X)
    want = laplacian_matmul(aff.Wp, X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_sparse_affinities_full_k_matches_dense():
    Y, _ = _problem()
    n = Y.shape[0]
    for model in ("ee", "tsne"):
        saff = sparse_affinities(Y, k=n - 1, perplexity=8.0, model=model)
        aff = make_affinities(Y, 8.0, model=model)
        A = to_dense(saff.graph)
        np.testing.assert_allclose(np.asarray(0.5 * (A + A.T)),
                                   np.asarray(aff.Wp), rtol=1e-4, atol=1e-8)


def test_truncated_k_calibration_rowsums():
    """Calibrated conditionals over k candidates are row-stochastic."""
    Y, _ = _problem()
    saff = sparse_affinities(Y, k=10, perplexity=5.0, model="ee")
    rows = jnp.sum(saff.graph.weights, axis=1)
    np.testing.assert_allclose(np.asarray(rows), 1.0, rtol=1e-4)


# -- energy/gradient parity -----------------------------------------------------


@pytest.mark.parametrize("kind,lam", UNNORM + NORM)
def test_sparse_energy_grad_matches_dense_oracle(kind, lam):
    """Acceptance criterion: <= 1e-4 relative agreement at kappa = N-1
    with exhaustive negatives, for every model family (normalized kinds
    go through the ratio-estimator path, exact in exhaustive mode)."""
    Y, X = _problem()
    n = Y.shape[0]
    aff = make_affinities(Y, 8.0, model=kind)
    saff = sparse_affinities(Y, k=n - 1, perplexity=8.0, model=kind)
    E1, G1 = energy_and_grad(X, aff, kind, lam)
    E2, G2 = energy_and_grad_sparse(X, saff, kind, lam, n_negatives=None)
    assert abs(float(E1 - E2)) / abs(float(E1)) < 1e-4
    relG = float(jnp.linalg.norm(G1 - G2) / jnp.linalg.norm(G1))
    assert relG < 1e-4, (kind, relG)


@pytest.mark.parametrize("kind,lam", NORM)
def test_normalized_sparse_parity_1e5(kind, lam):
    """Tentpole acceptance: sparse ssne/tsne match the dense path to
    <= 1e-5 energy/grad at k = N-1 with full negatives.  The graph is
    built FROM the dense weights so the comparison pins the estimator
    math itself, not the (separately tested) k-candidate calibration."""
    Y, X = _problem()
    n = Y.shape[0]
    aff = make_affinities(Y, 8.0, model=kind)
    g = from_dense(aff.Wp, k=n - 1)
    saff = SparseAffinities(graph=g, rev=reverse_graph(g))
    E1, G1 = energy_and_grad(X, aff, kind, lam)
    E2, G2 = energy_and_grad_sparse(X, saff, kind, lam, n_negatives=None)
    relE = abs(float(E1 - E2)) / abs(float(E1))
    relG = float(jnp.linalg.norm(G1 - G2) / jnp.linalg.norm(G1))
    assert relE <= 1e-5, (kind, relE)
    assert relG <= 1e-5, (kind, relG)
    # the line-search fast path computes the identical energy
    E3, _ = energy_and_grad_sparse(X, saff, kind, lam, n_negatives=None,
                                   with_grad=False)
    assert abs(float(E1 - E3)) / abs(float(E1)) <= 1e-5


@pytest.mark.parametrize("kind,lam", [("ee", 50.0), ("tee", 10.0)])
def test_negative_sampling_unbiased(kind, lam):
    Y, X = _problem()
    aff = make_affinities(Y, 8.0, model=kind)
    saff = sparse_affinities(Y, k=Y.shape[0] - 1, perplexity=8.0, model=kind)
    E_true, G_true = energy_and_grad(X, aff, kind, lam)
    Es, Gs = [], []
    for s in range(60):
        E, G = energy_and_grad_sparse(X, saff, kind, lam, n_negatives=8,
                                      key=jax.random.PRNGKey(s))
        Es.append(float(E))
        Gs.append(np.asarray(G))
    assert abs(np.mean(Es) - float(E_true)) / abs(float(E_true)) < 0.02
    # the 60-sample mean still carries ~sigma/sqrt(60) Monte-Carlo noise;
    # 0.1 is ~2x the measured value, far below the O(1) error of a biased
    # (uncorrected) estimator
    relG = (np.linalg.norm(np.mean(Gs, axis=0) - np.asarray(G_true))
            / np.linalg.norm(np.asarray(G_true)))
    assert relG < 0.1


@pytest.mark.parametrize("kind", ["ee", "tsne"])
def test_sampled_gradient_translation_invariant(kind):
    """Symmetric application of sampled edges => columns of G sum to ~0,
    for the absolute estimator (ee) and the ratio estimator (tsne)."""
    Y, X = _problem()
    saff = sparse_affinities(Y, k=10, perplexity=5.0, model=kind)
    _, G = energy_and_grad_sparse(X, saff, kind, 2.0, n_negatives=6,
                                  key=jax.random.PRNGKey(3))
    colsum = np.asarray(jnp.sum(G, axis=0))
    assert np.all(np.abs(colsum) < 1e-3 * float(jnp.max(jnp.abs(G))))


# -- ratio estimator for normalized models --------------------------------------


@pytest.mark.parametrize("kind,lam", NORM)
def test_partition_estimate_unbiased_over_seeds(kind, lam):
    """E[s_hat] = Z: the cyclic-shift draw with the (N-1)/m correction is
    an unbiased estimator of the global partition function."""
    Y, X = _problem()
    aff = make_affinities(Y, 8.0, model=kind)
    saff = sparse_affinities(Y, k=Y.shape[0] - 1, perplexity=8.0, model=kind)
    z_true = float(pairwise_terms_ref(X, aff.Wp, aff.Wm, kind).s)
    zs = [float(energy_and_grad_sparse(
            X, saff, kind, lam, n_negatives=8, key=jax.random.PRNGKey(s),
            return_state=True)[2]) for s in range(80)]
    # the 80-sample mean carries ~sigma/sqrt(80) Monte-Carlo noise; 0.05 is
    # far below the O(1) error of a biased (uncorrected) estimator
    assert abs(np.mean(zs) - z_true) / z_true < 0.05


def test_streaming_z_ema_update():
    """Sampled mode: z_new = decay * z_prev + (1 - decay) * s_hat once the
    state is initialized; an uninitialized (<= 0) state passes s_hat
    through; exhaustive mode bypasses the EMA entirely (z = s_hat = Z)."""
    Y, X = _problem()
    saff = sparse_affinities(Y, k=10, perplexity=5.0, model="ssne")
    k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    args = dict(n_negatives=6, return_state=True)
    _, _, s1 = energy_and_grad_sparse(X, saff, "ssne", 1.0, key=k1, **args)
    _, _, s2 = energy_and_grad_sparse(X, saff, "ssne", 1.0, key=k2, **args)
    # warm state: EMA of the previous z and this draw's s_hat
    _, _, z = energy_and_grad_sparse(X, saff, "ssne", 1.0, key=k2,
                                     z_prev=s1, z_decay=0.7, **args)
    np.testing.assert_allclose(float(z), 0.7 * float(s1) + 0.3 * float(s2),
                               rtol=1e-6)
    # uninitialized state (<= 0 sentinel): the draw's own estimate
    _, _, z0 = energy_and_grad_sparse(X, saff, "ssne", 1.0, key=k2,
                                      z_prev=jnp.zeros(()), z_decay=0.7,
                                      **args)
    np.testing.assert_allclose(float(z0), float(s2), rtol=1e-6)
    # exhaustive negatives: Z is exact, the EMA is bypassed
    _, _, ze = energy_and_grad_sparse(X, saff, "ssne", 1.0,
                                      n_negatives=None, z_prev=s1,
                                      z_decay=0.7, return_state=True)
    _, _, ze2 = energy_and_grad_sparse(X, saff, "ssne", 1.0,
                                       n_negatives=None, return_state=True)
    np.testing.assert_array_equal(np.asarray(ze), np.asarray(ze2))


def test_normalized_kinds_now_supported():
    """The pre-estimator explicit ValueError is lifted: normalized kinds
    run through the sparse path (sampled and exhaustive)."""
    Y, X = _problem(n=12)
    saff = sparse_affinities(Y, k=5, perplexity=3.0, model="ssne")
    E, G = energy_and_grad_sparse(X, saff, "ssne", 1.0, n_negatives=5,
                                  key=jax.random.PRNGKey(0))
    assert np.isfinite(float(E)) and np.all(np.isfinite(np.asarray(G)))
    # return_state is estimator plumbing: meaningless for unnormalized kinds
    with pytest.raises(ValueError, match="normalized"):
        energy_and_grad_sparse(X, saff, "ee", 1.0, n_negatives=None,
                               return_state=True)


# -- spectral direction ---------------------------------------------------------


def test_sparse_sd_matches_cholesky_sd():
    """Jacobi-CG solve from ELL storage vs the dense Cholesky backsolve."""
    Y, X = _problem()
    aff = make_affinities(Y, 8.0, model="ee")
    G = jax.random.normal(jax.random.PRNGKey(5), X.shape)
    sd = SD()
    P1, _ = sd.direction(sd.init(X, aff, "ee", 50.0), X, G, aff, "ee", 50.0)
    ssd = SparseSD(cg_tol=1e-6, cg_maxiter=500)
    P2, _ = ssd.direction(ssd.init(X, aff, "ee", 50.0), X, G, aff, "ee", 50.0)
    rel = float(jnp.linalg.norm(P1 - P2) / jnp.linalg.norm(P1))
    assert rel < 5e-3, rel


def test_sparse_sd_native_graph_descends():
    """A dense fit with SparseSD initialized from SparseAffinities state."""
    from repro.core import LSConfig
    from tests.conftest import dense_fit
    Y = three_loops(n_per=24, loops=2, dim=8)
    aff = make_affinities(Y, 10.0, model="ee")
    X0 = jax.random.normal(jax.random.PRNGKey(0), (Y.shape[0], 2)) * 0.1
    res = dense_fit(X0, aff, "ee", 50.0, make_strategy("sparsesd"),
                    max_iters=20, ls_cfg=LSConfig(init_step="adaptive_grow"))
    assert res.energies[-1] < 0.5 * res.energies[0]


def test_pcg_solves_spd_system():
    n, d = 30, 3
    key = jax.random.PRNGKey(0)
    M = jax.random.normal(key, (n, n))
    A = M @ M.T + n * jnp.eye(n)
    B = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    res = pcg(lambda V: A @ V, B, jnp.zeros_like(B),
              inv_diag=1.0 / jnp.diag(A), tol=1e-7, maxiter=400)
    np.testing.assert_allclose(np.asarray(res.x),
                               np.asarray(jnp.linalg.solve(A, B)),
                               rtol=1e-3, atol=1e-4)


# -- sparse spectral init -------------------------------------------------------


def test_sparse_eigenmaps_matches_dense():
    """Power-iteration eigenmaps from ELL storage vs the dense eigh on the
    same symmetrized graph: each embedding column matches the corresponding
    dense eigenvector up to sign (ROADMAP: sparse spectral init)."""
    from repro.core import laplacian_eigenmaps

    Y = three_loops(n_per=30, loops=2, dim=8)
    saff = sparse_affinities(Y, k=12, perplexity=4.0, model="ee")
    A = to_dense(saff.graph)
    Xd = np.asarray(laplacian_eigenmaps(0.5 * (A + A.T), 2))
    Xs = np.asarray(sparse_laplacian_eigenmaps(saff.graph, saff.rev, d=2))
    for j in range(2):
        c = abs(np.dot(Xd[:, j], Xs[:, j])
                / (np.linalg.norm(Xd[:, j]) * np.linalg.norm(Xs[:, j])))
        assert c > 0.99, (j, c)


def test_sparse_init_routes_to_power_iteration_above_cutoff():
    """Above N = 2048 the sparse builders' spectral init is the ELL power
    iteration, not the former random fallback."""
    from repro.api import EmbedSpec
    from repro.embed.trainer import _sparse_spectral_init

    n = 2100
    Y = jax.random.normal(jax.random.PRNGKey(0), (n, 4))
    saff = sparse_affinities(Y, k=12, perplexity=4.0, model="ee")
    X0 = _sparse_spectral_init(EmbedSpec(perplexity=4.0, n_neighbors=12),
                               saff, n)
    want = sparse_laplacian_eigenmaps(saff.graph, saff.rev, d=2, seed=0) * 0.1
    np.testing.assert_array_equal(np.asarray(X0), np.asarray(want))


# -- trainer integration --------------------------------------------------------


def test_trainer_sparse_path_descends():
    from repro.api import Embedding, EmbedSpec
    Y = three_loops(n_per=24, loops=2, dim=8)
    spec = EmbedSpec(kind="ee", lam=50.0, perplexity=8.0, max_iters=15,
                     backend="sparse", n_neighbors=20, n_negatives=8)
    res = Embedding(spec).fit(Y).result_
    assert res.energies[-1] < res.energies[0]
    assert res.X.shape == (Y.shape[0], 2)


@pytest.mark.parametrize("kind", ["ssne", "tsne"])
def test_trainer_sparse_normalized_descends(kind):
    """The sparse backend with a normalized kind routes through the
    ratio-estimator objective."""
    from repro.api import Embedding, EmbedSpec
    Y = three_loops(n_per=24, loops=2, dim=8)
    spec = EmbedSpec(kind=kind, lam=1.0, perplexity=8.0, max_iters=15,
                     backend="sparse", n_neighbors=20, n_negatives=8)
    res = Embedding(spec).fit(Y).result_
    assert res.energies[-1] < res.energies[0]
    assert res.X.shape == (Y.shape[0], 2)
    assert np.all(np.isfinite(res.energies))


# -- cross-set kNN (serving path: queries vs the frozen training set) -----------


def test_knn_cross_exact_matches_brute_force():
    Yr, _ = _problem(n=40)
    Yq, _ = _problem(n=13, seed=7)
    d2, idx = knn_cross(Yq, Yr, 5, block_rows=4)
    D2 = np.array(jnp.sum((Yq[:, None] - Yr[None]) ** 2, axis=-1))
    for i in range(Yq.shape[0]):
        want = set(np.argsort(D2[i])[:5])
        assert set(np.asarray(idx[i])) == want, i
    np.testing.assert_allclose(np.asarray(d2),
                               np.sort(D2, axis=1)[:, :5], rtol=1e-5)


def test_knn_cross_validates_k_up_front():
    Yr, _ = _problem(n=10)
    Yq, _ = _problem(n=3, seed=1)
    with pytest.raises(ValueError, match="k >= 1"):
        knn_cross(Yq, Yr, 0)
    # the error names the training-set size and the fix, before any
    # blocked distance work runs
    with pytest.raises(ValueError, match="n_train=10"):
        knn_cross(Yq, Yr, 11)
    with pytest.raises(ValueError, match="n_train=10"):
        knn_cross(Yq, Yr, 11, method="approx")


def test_knn_cross_approx_recall_on_clustered_data():
    """Random-projection candidate windows recover >= 90% of the true
    cross-neighbors on clustered data (the regime serving cares about:
    queries near the training manifold)."""
    rng = np.random.default_rng(3)
    cents = rng.standard_normal((6, 8)) * 6
    Yr = jnp.asarray((cents[rng.integers(0, 6, 300)]
                      + rng.standard_normal((300, 8)) * 0.4)
                     .astype(np.float32))
    Yq = jnp.asarray((cents[rng.integers(0, 6, 40)]
                      + rng.standard_normal((40, 8)) * 0.4)
                     .astype(np.float32))
    k = 8
    _, idx_e = knn_cross(Yq, Yr, k, method="exact")
    _, idx_a = knn_cross(Yq, Yr, k, method="approx", n_projections=12,
                         window=24)
    hits = sum(len(set(np.asarray(idx_e[i]))
                   & set(np.asarray(idx_a[i])))
               for i in range(Yq.shape[0]))
    recall = hits / (Yq.shape[0] * k)
    assert recall >= 0.9, recall


def test_knn_cross_approx_duplicate_slots_are_inf():
    """Candidate-union slots beyond the distinct candidates carry +inf
    distances: downstream per-row calibration gives them exactly-zero
    weight (the padded-slot convention of the ELL graph)."""
    Yr, _ = _problem(n=6)
    Yq, _ = _problem(n=4, seed=2)
    # k == n_r with tiny windows forces duplicate-marked slots
    d2, idx = knn_cross(Yq, Yr, 6, method="approx", n_projections=4,
                        window=8)
    d2 = np.asarray(d2)
    finite = np.isfinite(d2)
    # every query found all 6 distinct references (windows cover the set)
    assert finite.sum(axis=1).min() == 6
    from repro.sparse import calibrated_weights_ell
    w = np.asarray(calibrated_weights_ell(
        jnp.asarray(d2), jnp.ones_like(jnp.asarray(idx), bool), 3.0))
    assert np.all(w[~finite] == 0.0)


def test_knn_cross_auto_threshold_dispatch(monkeypatch):
    """'auto' switches exact -> approx at CROSS_APPROX_N (the serving
    policy: no full scans against large frozen training sets)."""
    from repro.sparse import graph as graph_mod

    Yr, _ = _problem(n=50)
    Yq, _ = _problem(n=5, seed=4)
    calls = {}
    real_exact = graph_mod.knn_cross_exact
    real_approx = graph_mod.knn_cross_approx
    monkeypatch.setattr(
        graph_mod, "knn_cross_exact",
        lambda *a, **kw: calls.setdefault("m", "exact")
        or real_exact(*a, **kw))
    monkeypatch.setattr(
        graph_mod, "knn_cross_approx",
        lambda *a, **kw: calls.setdefault("m", "approx")
        or real_approx(*a, **kw))
    graph_mod.knn_cross(Yq, Yr, 4, method="auto")
    assert calls.pop("m") == "exact"
    monkeypatch.setattr(graph_mod, "CROSS_APPROX_N", 20)
    graph_mod.knn_cross(Yq, Yr, 4, method="auto")
    assert calls.pop("m") == "approx"
