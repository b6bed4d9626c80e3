"""Barnes-Hut far-field subsystem (sparse/farfield.py, docs/farfield.md):
grid-partition exactness, tree-vs-dense repulsion parity, determinism,
and the `tree` backend end to end through `repro.api.Embedding`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Embedding, EmbedSpec
from repro.kernels import ops
from repro.kernels.ref import KINDS, bh_interaction_ref, negative_pair_terms
from repro.sparse import (GridPlan, energy_and_grad_tree, make_grid_plan,
                          sparse_affinities, tree_diagnostics,
                          tree_repulsion)

SMOOTH = ("ee", "ssne", "tsne", "tee")   # epan's b = [t < 1] is a
                                         # discontinuous indicator: its
                                         # far-field FORCE aggregates badly
                                         # at the support boundary, so only
                                         # its repulsive SUM is pinned


def _cloud(n, seed=0, scale=1.0):
    """A 2-D cloud with clusters — uneven cell occupancy stresses the
    near-field cap + residual-COM path more than a uniform blob."""
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    centers = jax.random.normal(k1, (4, 2)) * 2.0
    X = centers[jnp.arange(n) % 4] + jax.random.normal(k2, (n, 2)) * 0.4
    return (X * scale).astype(jnp.float32)


def _dense_repulsion(X, kind):
    """O(N^2) oracle: ordered-pair repulsive sum and force field."""
    diff = X[:, None, :] - X[None, :, :]
    t = jnp.sum(diff * diff, axis=-1)
    sp, b = negative_pair_terms(kind, t)
    off = 1.0 - jnp.eye(X.shape[0], dtype=X.dtype)
    s = jnp.sum(off * sp)
    F = jnp.sum((off * b)[:, :, None] * diff, axis=1)
    return s, F


# -- plan construction ----------------------------------------------------------


def test_grid_plan_validation():
    with pytest.raises(ValueError, match="theta"):
        make_grid_plan(100, theta=1.5)
    with pytest.raises(ValueError, match="theta"):
        make_grid_plan(100, theta=-0.1)
    with pytest.raises(ValueError, match="n="):
        make_grid_plan(1)
    with pytest.raises(ValueError, match="chunk"):
        make_grid_plan(100, chunk=0)
    # theta=0.5 -> r=2 -> coarsest usable level l1=2: shallower grids
    # cannot express the opening criterion
    with pytest.raises(ValueError, match="depth"):
        make_grid_plan(100, theta=0.5, depth=1)


def test_grid_plan_theta_zero_is_exhaustive():
    plan = make_grid_plan(64, theta=0.0)
    assert plan.exhaustive and plan.r == 0


def test_tree_repulsion_rejects_non_2d():
    plan = make_grid_plan(32)
    X3 = jnp.zeros((32, 3), jnp.float32)
    with pytest.raises(ValueError, match="2-D"):
        tree_repulsion(X3, plan, "tsne")


def test_spec_validates_tree_knobs():
    with pytest.raises(ValueError, match="theta"):
        EmbedSpec(theta=2.0)
    with pytest.raises(ValueError, match="tree_depth"):
        EmbedSpec(tree_depth=-1)
    with pytest.raises(ValueError, match="tree_cap"):
        EmbedSpec(tree_cap=-3)


# -- parity against the dense oracle --------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_theta_zero_matches_dense(kind):
    X = _cloud(96, seed=1)
    plan = make_grid_plan(96, theta=0.0)
    s, F = tree_repulsion(X, plan, kind)
    s_ref, F_ref = _dense_repulsion(X, kind)
    assert abs(float(s - s_ref)) <= 1e-4 * abs(float(s_ref))
    # the tree sums F in the Laplacian form (sum_j w b) x_n - sum_j w b x_j,
    # the oracle sums w b (x_n - x_j): the two differ by f32 rounding that
    # grows with the n - 1 summed pairs and with the force scale, so the
    # absolute pin is n * eps(f32) * max|F| (an entry near zero can carry
    # the rounding of its large neighbours), beside the 1e-4 relative one
    atol = (X.shape[0] * float(jnp.finfo(jnp.float32).eps)
            * float(jnp.max(jnp.abs(F_ref))))
    np.testing.assert_allclose(np.asarray(F), np.asarray(F_ref),
                               rtol=1e-4, atol=atol)


@pytest.mark.parametrize("kind", KINDS)
def test_default_theta_repulsive_sum_within_1pct(kind):
    X = _cloud(600, seed=2)
    plan = make_grid_plan(600)            # theta = 0.5
    s, F = tree_repulsion(X, plan, kind)
    s_ref, F_ref = _dense_repulsion(X, kind)
    assert abs(float(s - s_ref)) <= 1e-2 * abs(float(s_ref)), \
        (kind, float(s), float(s_ref))
    if kind in SMOOTH:
        err = float(jnp.sqrt(jnp.mean((F - F_ref) ** 2)))
        ref = float(jnp.sqrt(jnp.mean(F_ref ** 2)))
        assert err <= 2e-2 * ref, (kind, err, ref)


@pytest.mark.parametrize("kind", ["ee", "tsne"])
def test_theta_zero_gradient_matches_autodiff(kind):
    """At theta=0 the tree energy is the exact objective, so the closed
    G = 4 (La x - lam_rep F) must equal autodiff of the dense energy."""
    n = 72
    Y = jax.random.normal(jax.random.PRNGKey(3), (n, 8))
    X = _cloud(n, seed=4, scale=0.5)
    saff = sparse_affinities(Y, k=8, perplexity=3.0, model=kind)
    plan = make_grid_plan(n, theta=0.0)
    lam = jnp.float32(2.0)
    E, G = energy_and_grad_tree(X, saff, lam, kind, plan)

    from repro.core.objectives import is_normalized, sparse_attractive_terms

    def dense_energy(X):
        e_plus, _ = sparse_attractive_terms(X, saff, kind)
        s, _ = _dense_repulsion(X, kind)
        return e_plus + lam * (jnp.log(s) if is_normalized(kind) else s)

    E_ref, G_ref = jax.value_and_grad(dense_energy)(X)
    assert abs(float(E - E_ref)) <= 1e-4 * abs(float(E_ref))
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_ref),
                               rtol=2e-3, atol=1e-4)


# -- partition invariants -------------------------------------------------------


@pytest.mark.parametrize("n", [97, 600])
def test_partition_counts_every_ordered_pair_exactly_once(n):
    X = _cloud(n, seed=5)
    d = tree_diagnostics(X, make_grid_plan(n))
    assert float(d["tree_pairs"]) == n * (n - 1)
    # realized opening ratio never exceeds the requested theta
    assert float(d["tree_theta_ratio"]) <= 0.5 + 1e-6
    assert float(d["tree_overflow"]) >= 0.0


def test_partition_exact_under_degenerate_geometry():
    # a packed cluster plus far outliers: the outliers stretch the
    # bounding box so the cluster collapses into one finest cell, the
    # listed-slot cap overflows, and the residual-COM batch must carry
    # the excess weight (the bbox is data-adaptive, so a uniformly tiny
    # cloud alone would just be rescaled onto the full grid)
    cluster = jax.random.normal(jax.random.PRNGKey(6), (120, 2)) * 1e-3
    outliers = jnp.asarray([[10.0, 10.0]]) + \
        jax.random.normal(jax.random.PRNGKey(7), (8, 2))
    X = jnp.concatenate([cluster, outliers]).astype(jnp.float32)
    d = tree_diagnostics(X, make_grid_plan(128))
    assert float(d["tree_pairs"]) == 128 * 127
    assert float(d["tree_overflow"]) > 0.0


# -- determinism ----------------------------------------------------------------


def test_tree_repulsion_bit_identical_across_calls():
    X = _cloud(300, seed=7)
    plan = make_grid_plan(300)
    s1, F1 = tree_repulsion(X, plan, "tsne")
    s2, F2 = tree_repulsion(X, plan, "tsne")
    assert float(s1) == float(s2)
    assert np.array_equal(np.asarray(F1), np.asarray(F2))


# -- kernel dispatch ------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_bh_interaction_impls_agree(kind):
    key = jax.random.PRNGKey(8)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n, w_cols, m = 70, 12, 24
    X = jax.random.normal(k1, (n, 2))
    table = jax.random.normal(k2, (m, 2)) * 1.5
    idx = jax.random.randint(k3, (n, w_cols), 0, m)
    w = jnp.where(jax.random.uniform(k4, (n, w_cols)) < 0.3, 0.0,
                  1.0 + jnp.arange(w_cols, dtype=jnp.float32))
    s_ref, F_ref = bh_interaction_ref(X, idx, w, table, kind)
    for impl in ("jnp", "pallas-interpret"):
        s, F = ops.bh_interaction(X, idx, w, table, kind, impl=impl)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=5e-5, atol=1e-5, err_msg=impl)
        np.testing.assert_allclose(np.asarray(F), np.asarray(F_ref),
                                   rtol=5e-5, atol=1e-5, err_msg=impl)


def test_bh_interaction_zero_weight_masks_exactly():
    # w = 0 must contribute nothing even at t = 0 (self-interaction slots
    # point at the row's own coordinates)
    X = jnp.ones((8, 2), jnp.float32)
    idx = jnp.zeros((8, 4), jnp.int32)
    w = jnp.zeros((8, 4), jnp.float32)
    s, F = ops.bh_interaction(X, idx, w, X, "ee", impl="jnp")
    assert float(jnp.sum(jnp.abs(s))) == 0.0
    assert float(jnp.sum(jnp.abs(F))) == 0.0


# -- the tree backend end to end ------------------------------------------------


@pytest.fixture(scope="module")
def tree_problem():
    Y = jax.random.normal(jax.random.PRNGKey(9), (220, 10))
    spec = EmbedSpec(kind="tsne", strategy="sd", backend="tree", lam=1.0,
                     perplexity=5.0, n_neighbors=12, max_iters=15, tol=0.0,
                     kernel_impl="jnp")
    return Y, spec


def test_tree_fit_converges_and_is_deterministic(tree_problem):
    Y, spec = tree_problem
    emb1 = Embedding(spec).fit(Y)
    emb2 = Embedding(spec).fit(Y)
    E = np.asarray(emb1.result_.energies)
    assert E[-1] < E[0]
    # the engine line-searches, so the trajectory is monotone
    assert np.all(np.diff(E) <= 1e-5 * np.abs(E[:-1]) + 1e-8)
    # deterministic: no PRNG anywhere in the iteration -> bit-identical
    assert np.array_equal(np.asarray(emb1.embedding_),
                          np.asarray(emb2.embedding_))


def test_tree_fit_diagnostics_carry_partition_invariant(tree_problem):
    Y, spec = tree_problem
    emb = Embedding(spec).fit(Y, telemetry=True)
    d = emb.result_.diagnostics[-1]
    assert d["tree_pairs"] == Y.shape[0] * (Y.shape[0] - 1)
    assert {"pcg_iters", "tree_cells", "tree_overflow",
            "tree_theta_ratio"} <= set(d)
    # the grid rebuild shows up as a phase span; spans fire at trace
    # time, so assert on a cold trace (an unseen chunk width forces one)
    # rather than on the fit above, whose program may already be cached
    from repro.obs import Telemetry, activate

    tel = Telemetry()
    with activate(tel.tracer):
        plan = make_grid_plan(64, chunk=97)
        tree_repulsion(_cloud(64, seed=15), plan, "tsne")
    assert any(p["name"] == "grid-build"
               for p in tel.recorder.phases)


def test_tree_backend_rejects_non_2d_spec():
    Y = jax.random.normal(jax.random.PRNGKey(10), (64, 6))
    spec = EmbedSpec(kind="tsne", backend="tree", dim=3, perplexity=3.0,
                     max_iters=3)
    with pytest.raises(ValueError, match="2-D only"):
        Embedding(spec).fit(Y)


def test_tree_theta_knob_changes_plan_not_validity(tree_problem):
    Y, spec = tree_problem
    emb = Embedding(spec.replace(theta=0.25, max_iters=5)).fit(
        Y, telemetry=True)
    d = emb.result_.diagnostics[-1]
    assert d["tree_pairs"] == Y.shape[0] * (Y.shape[0] - 1)
    assert d["tree_theta_ratio"] <= 0.25 + 1e-6


# -- precomputed saff= (shared k-NN build) --------------------------------------


def test_fit_saff_matches_internal_build_bit_for_bit():
    Y = jax.random.normal(jax.random.PRNGKey(11), (180, 8))
    spec = EmbedSpec(kind="ee", strategy="sd", backend="sparse", lam=50.0,
                     perplexity=4.0, n_neighbors=12, max_iters=8, tol=0.0)
    saff = sparse_affinities(Y, k=12, perplexity=4.0, model="ee")
    emb_a = Embedding(spec).fit(Y)
    emb_b = Embedding(spec).fit(Y, saff=saff)
    assert np.array_equal(np.asarray(emb_a.embedding_),
                          np.asarray(emb_b.embedding_))


def test_fit_saff_pins_sparse_backend_under_auto():
    Y = jax.random.normal(jax.random.PRNGKey(12), (96, 6))
    saff = sparse_affinities(Y, k=8, perplexity=3.0, model="tsne")
    emb = Embedding(EmbedSpec(kind="tsne", perplexity=3.0, n_neighbors=8,
                              max_iters=3, lam=1.0)).fit(Y, saff=saff)
    assert emb.backend_ == "sparse"


def test_fit_saff_on_tree_backend(tree_problem):
    Y, spec = tree_problem
    saff = sparse_affinities(Y, k=12, perplexity=5.0, model="tsne")
    emb_a = Embedding(spec.replace(max_iters=6)).fit(Y)
    emb_b = Embedding(spec.replace(max_iters=6)).fit(Y, saff=saff)
    assert np.array_equal(np.asarray(emb_a.embedding_),
                          np.asarray(emb_b.embedding_))


def test_fit_rejects_aff_saff_combinations():
    Y = jax.random.normal(jax.random.PRNGKey(13), (40, 5))
    saff = sparse_affinities(Y, k=6, perplexity=2.0, model="ee")
    with pytest.raises(ValueError, match="not.*both|not both"):
        Embedding(EmbedSpec(kind="ee")).fit(Y, aff=object(), saff=saff)
    with pytest.raises(ValueError, match="dense backend"):
        Embedding(EmbedSpec(kind="ee", backend="dense")).fit(Y, saff=saff)
    with pytest.raises(ValueError, match="sparse-sharded"):
        Embedding(EmbedSpec(kind="ee", backend="sparse-sharded",
                            perplexity=2.0)).fit(Y, saff=saff)


def test_fit_saff_validates_matching_n():
    Y = jax.random.normal(jax.random.PRNGKey(14), (40, 5))
    saff = sparse_affinities(Y[:30], k=6, perplexity=2.0, model="ee")
    with pytest.raises(ValueError, match="n"):
        Embedding(EmbedSpec(kind="ee", backend="sparse")).fit(Y, saff=saff)
