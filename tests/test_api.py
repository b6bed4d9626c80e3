"""The unified `repro.api` estimator surface: registry validation,
strategy parity against a direct engine fit, backend auto-resolution,
out-of-sample transform semantics and the versioned artifact format
(save/load)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Embedding, EmbedSpec, TransformSpec, \
    available_backends, available_strategies, read_header, resolve_backend
from repro.core import LSConfig, laplacian_eigenmaps, make_affinities
from repro.core.strategies import DiagH, FP, GD, SD, SDMinus
from repro.data import mnist_like
from tests.conftest import dense_fit, three_loops


@pytest.fixture(scope="module")
def problem():
    Y = three_loops(n_per=16, loops=2, dim=8)
    aff = make_affinities(Y, 8.0, model="ee")
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1
    return Y, aff, X0


# -- early validation (satellite: reject unknown names at construction) --------


def test_spec_rejects_unknown_kind():
    with pytest.raises(ValueError, match="tsne"):
        EmbedSpec(kind="nope")


def test_spec_rejects_unknown_strategy_with_registry_names():
    with pytest.raises(ValueError) as e:
        EmbedSpec(strategy="newton")
    for name in ("gd", "fp", "diag", "sd", "sd-"):
        assert f"'{name}'" in str(e.value)


def test_spec_rejects_unknown_backend_with_registry_names():
    with pytest.raises(ValueError) as e:
        EmbedSpec(backend="gpu")
    for name in available_backends():
        assert f"'{name}'" in str(e.value)


def test_spec_rejects_incompatible_strategy_backend():
    with pytest.raises(ValueError, match="not available on backend"):
        EmbedSpec(strategy="sd-", backend="sparse")
    # auto never errors at construction: it falls back to dense at resolve
    EmbedSpec(strategy="sd-", backend="auto")


def test_spec_strategy_aliases():
    assert EmbedSpec(strategy="DiagH").strategy == "diag"
    assert EmbedSpec(strategy="L-BFGS").strategy == "lbfgs"


def test_spec_rejects_unknown_kernel_knobs():
    with pytest.raises(ValueError, match="kernel_impl"):
        EmbedSpec(kernel_impl="cuda")
    with pytest.raises(ValueError, match="kernel_precision"):
        EmbedSpec(kernel_precision="float16")


def test_spec_kernel_args_empty_at_defaults():
    """Default kernel knobs forward NOTHING, so the dispatchers pick for
    themselves (the bit-for-bit parity tests below depend on it)."""
    assert EmbedSpec().kernel_args() == {}
    assert EmbedSpec(kernel_impl="jnp").kernel_args() == {"impl": "jnp"}
    assert EmbedSpec(kernel_precision="bfloat16").kernel_args() == \
        {"storage_dtype": "bfloat16"}


def test_dense_fit_through_interpret_kernel(problem):
    """EmbedSpec.kernel_impl routes the dense objective's pairwise terms
    through the Pallas (interpret) kernel; trajectories track the jnp
    path to f32 accumulation noise, and bf16 storage runs end-to-end."""
    from repro.kernels import ops

    Y, aff, X0 = problem
    base = EmbedSpec(kind="ee", lam=50.0, strategy="sd", backend="dense",
                     max_iters=3, tol=0.0)
    r0 = Embedding(base).fit(None, X0=X0, aff=aff).result_
    rk = Embedding(base.replace(kernel_impl="pallas-interpret")).fit(
        None, X0=X0, aff=aff).result_
    np.testing.assert_allclose(rk.energies, r0.energies, rtol=1e-4)
    disp = ops.last_dispatch("pairwise_terms")
    assert disp["path"] == "pallas" and disp["reason"] == "forced-on"

    rb = Embedding(base.replace(kernel_impl="pallas-interpret",
                                kernel_precision="bfloat16")).fit(
        None, X0=X0, aff=aff).result_
    assert np.isfinite(rb.energies).all()
    assert rb.energies[-1] < rb.energies[0]
    assert ops.last_dispatch("pairwise_terms")["storage"] == "bfloat16"


def test_spec_rejection_messages_name_the_registries():
    with pytest.raises(ValueError, match="model families"):
        EmbedSpec(kind="nope")
    with pytest.raises(ValueError, match="registered strategies"):
        EmbedSpec(strategy="newton")


def test_auto_backend_resolution():
    assert resolve_backend("auto", n=500, n_devices=1, strategy="sd") \
        == "dense"
    assert resolve_backend("auto", n=512, n_devices=8, strategy="sd") \
        == "dense-mesh"
    # dense-mesh shards (N, N) without padding: indivisible N stays dense
    assert resolve_backend("auto", n=500, n_devices=8, strategy="sd") \
        == "dense"
    assert resolve_backend("auto", n=50_000, n_devices=1, strategy="sd") \
        == "sparse"
    assert resolve_backend("auto", n=50_000, n_devices=8, strategy="sd") \
        == "sparse-sharded"
    # dense-only strategies fall back to the dense backend at any scale
    assert resolve_backend("auto", n=50_000, n_devices=8, strategy="sd-") \
        == "dense"
    assert available_strategies() == sorted(available_strategies())


# -- strategy-registry parity (satellite) ---------------------------------------


@pytest.mark.parametrize("name,strategy", [
    ("gd", GD()),
    ("fp", FP()),
    ("diag", DiagH()),
    ("sd", SD()),
    ("sd-", SDMinus()),
])
def test_dense_strategy_parity_bit_for_bit(problem, name, strategy):
    """Every registered partial-Hessian strategy through repro.api matches
    a direct engine fit of the `core/strategies` object bit-for-bit: the
    registry maps each name onto exactly that object."""
    _, aff, X0 = problem
    ls = LSConfig(init_step="adaptive_grow" if name.startswith("sd")
                  else "one")
    ref = dense_fit(X0, aff, "ee", 50.0, strategy, max_iters=10, tol=1e-6,
                    ls_cfg=ls)
    emb = Embedding(EmbedSpec(kind="ee", lam=50.0, strategy=name,
                              backend="dense", max_iters=10, tol=1e-6,
                              ls=ls))
    emb.fit(None, X0=X0, aff=aff)
    res = emb.result_
    np.testing.assert_array_equal(np.asarray(ref.X),
                                  np.asarray(emb.embedding_))
    assert list(ref.energies) == list(res.energies)
    assert list(ref.step_sizes) == list(res.step_sizes)
    assert list(ref.n_fevals) == list(res.n_fevals)


@pytest.fixture(scope="module")
def sparse_spec():
    return EmbedSpec(kind="ee", lam=50.0, strategy="sd", backend="sparse",
                     perplexity=8.0, max_iters=8, tol=0.0,
                     n_neighbors=24, n_negatives=8)


@pytest.mark.parametrize("strategy", ["fp", "gd"])
def test_diagonal_strategies_on_sparse_backend(problem, sparse_spec,
                                               strategy):
    """The registry's diagonal degenerations run on the sparse backend and
    decrease energy (fp is the paper's fixed-point iteration realized from
    the Jacobi diagonal of the sparse SD system)."""
    Y, _, _ = problem
    res = Embedding(sparse_spec.replace(strategy=strategy)).fit(Y).result_
    assert np.all(np.isfinite(res.energies))
    assert res.energies[-1] < res.energies[0]


def test_sharded_backend_parity(problem, sparse_spec):
    """sd on the sparse-sharded backend tracks the single-device sparse
    backend within the existing parity pins (per-application <= 1e-5;
    trajectories to accumulated-fp rtol).  Runs on however many devices
    are visible — 8 in the multi-device CI job."""
    Y, _, _ = problem
    ndev = jax.device_count()
    from repro.launch.mesh import axis_types_kwargs
    mesh = jax.make_mesh((ndev, 1), ("data", "model"),
                         **axis_types_kwargs(2))
    r_sp = Embedding(sparse_spec).fit(Y).result_
    r_sh = Embedding(sparse_spec.replace(backend="sparse-sharded"),
                     mesh=mesh).fit(Y).result_
    np.testing.assert_allclose(r_sh.energies, r_sp.energies, rtol=5e-3)
    # the sharded normalized path (streaming-Z psum) stays in lockstep too
    t_sp = Embedding(sparse_spec.replace(kind="tsne", lam=1.0)).fit(Y)
    t_sh = Embedding(sparse_spec.replace(kind="tsne", lam=1.0,
                                         backend="sparse-sharded"),
                     mesh=mesh).fit(Y)
    np.testing.assert_allclose(t_sh.result_.energies, t_sp.result_.energies,
                               rtol=5e-3)


def test_dense_mesh_backend_strategies(problem):
    Y, _, _ = problem
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    energies = {}
    for strategy in ("sd", "fp", "gd"):
        res = Embedding(EmbedSpec(kind="ee", lam=50.0, strategy=strategy,
                                  backend="dense-mesh", perplexity=8.0,
                                  max_iters=6, tol=0.0),
                        mesh=mesh).fit(Y).result_
        assert res.energies[-1] < res.energies[0]
        energies[strategy] = res.energies[-1]
    # distinct directions actually ran (not one solver under three names)
    assert len({round(float(e), 3) for e in energies.values()}) == 3


# -- estimator surface ----------------------------------------------------------


def test_fit_transform_and_resume(tmp_path, problem):
    Y, _, _ = problem
    spec = EmbedSpec(kind="ee", lam=50.0, strategy="sd", backend="dense",
                     perplexity=8.0, max_iters=12, tol=0.0,
                     checkpoint_dir=str(tmp_path / "ck"),
                     checkpoint_every=100)
    full = Embedding(spec.replace(checkpoint_dir=None))
    X_full = full.fit_transform(Y)

    # interrupted at 6, resumed to 12: bit-identical trajectory
    part = Embedding(spec.replace(max_iters=6)).fit(Y)
    resumed = Embedding(spec).resume(Y)
    assert resumed.result_.resumed_from == 6
    np.testing.assert_array_equal(np.asarray(X_full),
                                  np.asarray(resumed.embedding_))
    np.testing.assert_array_equal(full.result_.energies[7:],
                                  resumed.result_.energies[1:])
    assert part.result_.n_iters == 6


def test_transform_leaves_training_embedding_bit_identical():
    Y, labels = mnist_like(n=240)
    emb = Embedding(EmbedSpec(kind="tsne", lam=1.0, strategy="sd",
                              backend="dense", perplexity=10.0,
                              max_iters=30, tol=0.0))
    emb.fit(jnp.asarray(Y[:200]))
    before = np.asarray(emb.embedding_).copy()
    X_new = emb.transform(jnp.asarray(Y[200:]),
                          spec=TransformSpec(max_iters=15))
    assert X_new.shape == (40, 2)
    assert np.all(np.isfinite(np.asarray(X_new)))
    np.testing.assert_array_equal(before, np.asarray(emb.embedding_))
    # and the fit result object was not touched either (no re-fit)
    assert emb.result_.n_iters == 30


def test_transform_places_heldout_mnist_near_own_class():
    """Acceptance: held-out MNIST digits land nearer their own class's
    training centroid than any other class's for >= 80% of points."""
    Y, labels = mnist_like(n=480)
    n_tr = 400
    l_tr, l_te = labels[:n_tr], labels[n_tr:]
    emb = Embedding(EmbedSpec(kind="tsne", lam=1.0, strategy="sd",
                              backend="dense", perplexity=15.0,
                              max_iters=60, tol=0.0))
    emb.fit(jnp.asarray(Y[:n_tr]))
    X = np.asarray(emb.embedding_)
    X_new = np.asarray(emb.transform(jnp.asarray(Y[n_tr:]),
                                     spec=TransformSpec(max_iters=40)))
    cents = np.stack([X[l_tr == c].mean(0) for c in range(10)])
    d = ((X_new[:, None, :] - cents[None]) ** 2).sum(-1)
    acc = float((d.argmin(1) == l_te).mean())
    assert acc >= 0.8, acc


def test_transform_exhaustive_is_deterministic():
    """n_negatives=None (or >= N) runs the anchored repulsion over EVERY
    training anchor: the objective is deterministic (no PRNG keys, raw
    convergence) and two transforms agree exactly."""
    from repro.api import TransformObjective

    Y, _ = mnist_like(n=130)
    emb = Embedding(EmbedSpec(kind="ee", lam=10.0, strategy="sd",
                              backend="dense", perplexity=8.0,
                              max_iters=15, tol=0.0))
    emb.fit(jnp.asarray(Y[:100]))
    tspec = TransformSpec(max_iters=10, exhaustive=True)
    a = emb.transform(jnp.asarray(Y[100:]), spec=tspec)
    b = emb.transform(jnp.asarray(Y[100:]), spec=tspec)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # None really selects the exhaustive mode (not the spec's 50-sample
    # default): the objective must come out deterministic
    anchors = jnp.asarray(emb.embedding_)
    obj = TransformObjective("ee", 10.0, anchors,
                             jnp.zeros((3, 4), jnp.int32),
                             jnp.full((3, 4), 0.25), None)
    assert obj.stochastic is False
    assert TransformObjective("ee", 10.0, anchors,
                              jnp.zeros((3, 4), jnp.int32),
                              jnp.full((3, 4), 0.25), 5).stochastic is True


def test_transform_empty_batch():
    """A zero-row serving batch returns a (0, dim) embedding, not a crash."""
    Y, _ = mnist_like(n=100)
    emb = Embedding(EmbedSpec(kind="ee", lam=10.0, strategy="sd",
                              backend="dense", perplexity=8.0,
                              max_iters=5, tol=0.0))
    emb.fit(jnp.asarray(Y))
    out = emb.transform(jnp.zeros((0, Y.shape[1])))
    assert np.asarray(out).shape == (0, 2)


def test_auto_backend_with_precomputed_aff_stays_dense(problem):
    """aff= is consumable only by the dense backend; auto must not route a
    large-N precomputed-affinity fit into the sparse path's rejection."""
    _, aff, X0 = problem
    emb = Embedding(EmbedSpec(kind="ee", lam=50.0, max_iters=3, tol=0.0))
    emb.fit(None, X0=X0, aff=aff)
    assert emb.backend_ == "dense"


# -- TransformSpec (satellite: frozen request-shaping config) -------------------


def test_transform_spec_validation_registry_style():
    with pytest.raises(ValueError, match="knn_method"):
        TransformSpec(knn_method="annoy")
    with pytest.raises(ValueError, match="solver"):
        TransformSpec(solver="newton")
    with pytest.raises(ValueError, match="max_iters"):
        TransformSpec(max_iters=-1)
    with pytest.raises(ValueError, match="n_projections"):
        TransformSpec(knn_method="approx", n_projections=0)
    with pytest.raises(ValueError, match="tol"):
        TransformSpec(tol=-0.5)
    # the error names the valid options, like every registry error
    with pytest.raises(ValueError, match="exact"):
        TransformSpec(knn_method="annoy")


def test_transform_spec_is_frozen_and_replaceable():
    t = TransformSpec(max_iters=7)
    with pytest.raises(dataclasses.FrozenInstanceError):
        t.max_iters = 9
    assert t.replace(solver="rowwise").solver == "rowwise"
    assert t.max_iters == 7


def test_transform_spec_resolves_deferred_fields_from_embedspec():
    from repro.api import resolve_transform_spec

    spec = EmbedSpec(transform_iters=33, transform_negatives=11, tol=2e-4)
    r = resolve_transform_spec(spec, TransformSpec())
    assert (r.max_iters, r.n_negatives, r.tol) == (33, 11, 2e-4)
    # explicit values win over the spec's defaults
    r2 = resolve_transform_spec(spec, TransformSpec(max_iters=5, tol=0.0))
    assert (r2.max_iters, r2.tol) == (5, 0.0)


def test_rowwise_solver_is_batch_composition_invariant():
    """The serving guarantee: a row's transform is identical whether it
    arrives alone or inside any batch (micro-batching/padding safety)."""
    Y, _ = mnist_like(n=160)
    emb = Embedding(EmbedSpec(kind="ee", lam=10.0, strategy="sd",
                              backend="dense", perplexity=8.0,
                              max_iters=10, tol=0.0))
    emb.fit(jnp.asarray(Y[:128]))
    Q = jnp.asarray(Y[128:])
    tspec = TransformSpec(solver="rowwise", max_iters=12)
    joint = np.asarray(emb.transform(Q, spec=tspec))
    single = np.stack([np.asarray(emb.transform(Q[i:i + 1], spec=tspec))[0]
                       for i in range(Q.shape[0])])
    np.testing.assert_allclose(single, joint, atol=1e-5)
    # chunked serving path (batch_size) agrees too
    chunked = np.asarray(emb.transform(
        Q, spec=tspec.replace(batch_size=5)))
    np.testing.assert_allclose(chunked, joint, atol=1e-5)


@pytest.mark.parametrize("exhaustive", [False, True])
def test_rowwise_rows_exact_across_batch_sizes(exhaustive):
    """Every rowwise transform runs in one fixed row-chunk shape, so a
    row's answer is BITWISE the same alone, in pairs, or in a 12-row call.
    A batch-sized program made them differ (by 19 for the sampled draw on
    this problem): XLA lowers reductions per shape and the per-row Armijo
    branch amplifies the last bit."""
    Y, _ = mnist_like(n=300, dim=8)
    Y = np.asarray(Y, np.float32)
    emb = Embedding(EmbedSpec(kind="ee", perplexity=8.0, n_neighbors=24,
                              max_iters=20, tol=0.0, seed=0)).fit(Y)
    rng = np.random.default_rng(2)
    Q = (Y[rng.choice(300, size=12, replace=False)]
         + rng.normal(scale=0.01, size=(12, 8)).astype(np.float32))
    tspec = TransformSpec(solver="rowwise", exhaustive=exhaustive)
    joint = np.asarray(emb.transform(Q, spec=tspec))
    for step in (1, 2, 5):
        parts = np.concatenate([np.asarray(emb.transform(Q[i:i + step],
                                                         spec=tspec))
                                for i in range(0, 12, step)])
        np.testing.assert_array_equal(parts, joint)


# -- versioned artifacts (tentpole: save/load surface) --------------------------


@pytest.fixture(scope="module")
def fitted_small():
    Y, _ = mnist_like(n=140)
    emb = Embedding(EmbedSpec(kind="ee", lam=10.0, strategy="sd",
                              backend="dense", perplexity=8.0,
                              max_iters=12, tol=0.0, seed=0))
    emb.fit(jnp.asarray(Y[:120]))
    return np.asarray(Y), emb


def test_artifact_roundtrip_transform_bit_identical(tmp_path, fitted_small):
    """fit -> save -> load -> transform must equal the in-process
    transform EXACTLY in the deterministic (exhaustive) mode — the
    acceptance criterion of the artifact format."""
    Y, emb = fitted_small
    path = str(tmp_path / "model.npz")
    assert emb.save(path) == path
    loaded = Embedding.load(path)
    np.testing.assert_array_equal(np.asarray(emb.embedding_),
                                  np.asarray(loaded.embedding_))
    assert loaded.spec == emb.spec
    tspec = TransformSpec(max_iters=8, exhaustive=True)
    a = np.asarray(emb.transform(jnp.asarray(Y[120:]), spec=tspec))
    b = np.asarray(loaded.transform(jnp.asarray(Y[120:]), spec=tspec))
    np.testing.assert_array_equal(a, b)
    # header carries the calibrated graph stats + provenance
    hdr = read_header(path)
    assert hdr["schema_version"] == 1
    assert hdr["graph"]["k"] >= 1
    assert hdr["train"]["storage"] == "snapshot"
    assert hdr["stats"]["backend"] == "dense"


def test_artifact_ref_mode_and_hash_verification(tmp_path, fitted_small):
    Y, emb = fitted_small
    yref = str(tmp_path / "Y.npy")
    np.save(yref, np.asarray(emb._Y_train))
    path = str(tmp_path / "ref.npz")
    emb.save(path, train="ref", train_ref=yref)
    # ref artifacts are small: no Y member inside
    with np.load(path) as z:
        assert "Y" not in z
    loaded = Embedding.load(path)
    np.testing.assert_array_equal(np.asarray(loaded._Y_train),
                                  np.asarray(emb._Y_train))
    # drifted reference data fails loudly on the stored SHA-256
    bad = np.array(np.load(yref))
    bad[0, 0] += 1.0
    np.save(yref, bad)
    with pytest.raises(ValueError, match="hash mismatch"):
        Embedding.load(path)
    # explicit Y_train= with the right bytes still loads
    ok = Embedding.load(path, Y_train=np.asarray(emb._Y_train))
    assert ok._Y_train is not None


def test_artifact_refuses_newer_schema(tmp_path, fitted_small):
    from repro.api.artifact import read_header as rh, write_artifact

    _, emb = fitted_small
    path = str(tmp_path / "future.npz")
    emb.save(path)
    hdr = rh(path)
    hdr["schema_version"] = 99
    hdr["from_the_future"] = True
    with np.load(path) as z:
        arrays = {k: np.array(z[k]) for k in z.files
                  if k != "__header__"}
    write_artifact(path, hdr, arrays)
    with pytest.raises(ValueError, match="newer than this"):
        Embedding.load(path)


def test_artifact_ignores_unknown_header_and_members(tmp_path,
                                                     fitted_small):
    """Append-only schema: extra header keys, extra spec fields and extra
    npz members from a forward-compatible v1 writer must load cleanly."""
    from repro.api.artifact import read_header as rh, write_artifact

    _, emb = fitted_small
    path = str(tmp_path / "forward.npz")
    emb.save(path)
    hdr = rh(path)
    hdr["new_toplevel_section"] = {"a": 1}
    hdr["spec"]["future_knob"] = "x"
    with np.load(path) as z:
        arrays = {k: np.array(z[k]) for k in z.files
                  if k != "__header__"}
    arrays["future_array"] = np.zeros(3)
    write_artifact(path, hdr, arrays)
    loaded = Embedding.load(path)
    np.testing.assert_array_equal(np.asarray(loaded.embedding_),
                                  np.asarray(emb.embedding_))


def test_artifact_golden_fixture_loads():
    """The committed golden artifact pins the on-disk schema: if this
    fails, a writer change broke the compatibility contract (readers of
    every v1 artifact ever written must keep working)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "golden_artifact_v1.npz")
    hdr = read_header(path)
    assert hdr["schema_version"] == 1
    est = Embedding.load(path)
    assert np.asarray(est.embedding_).shape == (32, 2)
    assert np.asarray(est._Y_train).shape == (32, 6)
    # and it actually serves: one exhaustive transform step runs
    out = est.transform(np.asarray(est._Y_train[:3]),
                        spec=TransformSpec(max_iters=2, exhaustive=True,
                                           solver="rowwise"))
    assert np.all(np.isfinite(np.asarray(out)))


def test_embedding_pickle_unsupported(fitted_small):
    import pickle

    _, emb = fitted_small
    with pytest.raises(TypeError, match="save"):
        pickle.dumps(emb)


def test_repr_shows_lifecycle(tmp_path, fitted_small):
    _, emb = fitted_small
    assert "unfitted" in repr(Embedding(EmbedSpec()))
    assert "fitted[dense]" in repr(emb)
    assert "n_train=120" in repr(emb)
    path = str(tmp_path / "r.npz")
    emb.save(path)
    r = repr(Embedding.load(path))
    assert "loaded[v1:" in r and path in r


def test_save_unfitted_or_affinity_only_rejected(problem):
    with pytest.raises(ValueError, match="fitted"):
        Embedding(EmbedSpec()).save("/tmp/nope.npz")
    _, aff, X0 = problem
    emb = Embedding(EmbedSpec(kind="ee", lam=50.0, max_iters=2, tol=0.0))
    emb.fit(None, X0=X0, aff=aff)
    with pytest.raises(ValueError, match="affinities"):
        emb.save("/tmp/nope.npz")
