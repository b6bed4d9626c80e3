"""Row-sharded sparse backend (sparse/sharding.py): multi-device parity via
a subprocess with 8 forced host devices (the main test process must keep
seeing 1 device), plus cheap in-process checks on a (1, 1) mesh."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import energy_and_grad_sparse
from repro.sparse import (make_sd_operator, make_sharded_energy_grad,
                          make_sharded_sd_operator, shard_sparse_affinities,
                          sparse_affinities, validate_sparse_mesh)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import axis_types_kwargs
    from repro.core import energy_and_grad_sparse
    from repro.api import Embedding, EmbedSpec
    from repro.sparse import (make_sd_operator, make_sharded_energy_grad,
                              make_sharded_sd_operator,
                              shard_sparse_affinities, sparse_affinities)
    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((8, 1), ("data", "model"), **axis_types_kwargs(2))

    n = 50                    # not divisible by 8: exercises row padding
    Y = jax.random.normal(jax.random.PRNGKey(0), (n, 6))
    X = jax.random.normal(jax.random.PRNGKey(1), (n, 2)) * 0.5

    # -- energy/gradient parity on an 8-way row shard ----------------------
    for kind, lam in [("ee", 50.0), ("tee", 10.0), ("epan", 5.0)]:
        saff = sparse_affinities(Y, k=10, perplexity=3.0, model=kind)
        sg = shard_sparse_affinities(mesh, ("data",), saff)
        for m in (5, None):
            eg, e_only = make_sharded_energy_grad(mesh, ("data",), sg, kind,
                                                  n_negatives=m)
            key = jax.random.PRNGKey(7)
            E1, G1 = energy_and_grad_sparse(X, saff, kind, lam,
                                            n_negatives=m, key=key)
            E2, G2 = eg(X, lam, key)
            relE = abs(float(E1 - E2)) / abs(float(E1))
            relG = float(jnp.linalg.norm(G1 - G2) / jnp.linalg.norm(G1))
            assert relE < 1e-5 and relG < 1e-5, (kind, m, relE, relG)
            relEo = abs(float(E1 - e_only(X, lam, key))) / abs(float(E1))
            assert relEo < 1e-5, (kind, m, relEo)

    # -- normalized kinds: ratio-estimator parity incl. the streaming Z ----
    for kind, lam in [("ssne", 5.0), ("tsne", 2.0)]:
        saff = sparse_affinities(Y, k=10, perplexity=3.0, model=kind)
        sg = shard_sparse_affinities(mesh, ("data",), saff)
        for m in (5, None):
            eg, e_only = make_sharded_energy_grad(mesh, ("data",), sg, kind,
                                                  n_negatives=m)
            key = jax.random.PRNGKey(7)
            E1, G1, z1 = energy_and_grad_sparse(
                X, saff, kind, lam, n_negatives=m, key=key,
                return_state=True)
            E8, G8, z8 = eg(X, lam, key, jnp.zeros(()))
            relE = abs(float(E1 - E8)) / abs(float(E1))
            relG = float(jnp.linalg.norm(G1 - G8) / jnp.linalg.norm(G1))
            relZ = abs(float(z1 - z8)) / abs(float(z1))
            assert relE < 1e-5 and relG < 1e-5 and relZ < 1e-5, \
                (kind, m, relE, relG, relZ)
            relEo = abs(float(E1 - e_only(X, lam, key))) / abs(float(E1))
            assert relEo < 1e-5, (kind, m, relEo)
            # warm streaming state, fresh key: the EMA'd lam/Z gradient
            # stays in lockstep across device counts
            key2 = jax.random.PRNGKey(8)
            E1b, G1b, z1b = energy_and_grad_sparse(
                X, saff, kind, lam, n_negatives=m, key=key2,
                z_prev=z1, return_state=True)
            E8b, G8b, z8b = eg(X, lam, key2, z8)
            relGb = float(jnp.linalg.norm(G1b - G8b)
                          / jnp.linalg.norm(G1b))
            relZb = abs(float(z1b - z8b)) / abs(float(z1b))
            assert relGb < 1e-5 and relZb < 1e-5, (kind, m, relGb, relZb)

    # -- SD operator parity ------------------------------------------------
    saff = sparse_affinities(Y, k=10, perplexity=3.0, model="ee")
    sg = shard_sparse_affinities(mesh, ("data",), saff)
    mv1, d1, mu1 = make_sd_operator(saff.graph, saff.rev, 1e-5)
    mv2, d2, mu2 = make_sharded_sd_operator(mesh, ("data",), sg, saff, 1e-5)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    assert float(mu1) == float(mu2)
    V = jax.random.normal(jax.random.PRNGKey(3), (n, 2))
    rel = float(jnp.linalg.norm(mv1(V) - mv2(V)) / jnp.linalg.norm(mv1(V)))
    assert rel < 1e-5, rel

    # -- acceptance: per-iteration energy/gradient parity along the actual
    # optimization trajectory (same seeds; <= 1e-5 relative) ---------------
    def three_loops(n_per, loops, dim, seed=0):
        ts = jnp.linspace(0, 2 * jnp.pi, n_per, endpoint=False)
        pts = []
        for i in range(loops):
            c = jax.random.normal(jax.random.PRNGKey(seed + 10 + i), (dim,)) * 3
            proj = jax.random.normal(jax.random.PRNGKey(seed + 20 + i), (2, dim))
            pts.append(jnp.stack([jnp.cos(ts), jnp.sin(ts)], -1) @ proj + c)
        return jnp.concatenate(pts)

    Y2 = three_loops(25, 2, 8)                       # n=50
    spec = EmbedSpec(kind="ee", lam=50.0, perplexity=8.0, max_iters=10,
                     backend="sparse", n_neighbors=24, n_negatives=8,
                     tol=0.0)
    sharded = spec.replace(backend="sparse-sharded")
    mesh1 = jax.make_mesh((1, 1), ("data", "model"), **axis_types_kwargs(2))
    iterates = []
    r1 = Embedding(spec, mesh=mesh1).fit(
        Y2, callback=lambda it, X, e: iterates.append(np.asarray(X))).result_

    saff2 = sparse_affinities(Y2, k=24, perplexity=8.0, model="ee")
    sg2 = shard_sparse_affinities(mesh, ("data",), saff2)
    eg8, _ = make_sharded_energy_grad(mesh, ("data",), sg2, "ee",
                                      n_negatives=8)
    key0 = jax.random.PRNGKey(spec.seed + 1)
    for it, Xt in enumerate(iterates, start=1):
        key = jax.random.fold_in(key0, it)
        E1, G1 = energy_and_grad_sparse(jnp.asarray(Xt), saff2, "ee", 50.0,
                                        n_negatives=8, key=key)
        E8, G8 = eg8(jnp.asarray(Xt), 50.0, key)
        relE = abs(float(E1 - E8)) / abs(float(E1))
        relG = float(jnp.linalg.norm(G1 - G8) / jnp.linalg.norm(G1))
        assert relE <= 1e-5 and relG <= 1e-5, (it, relE, relG)

    # -- end-to-end: the sharded backend on 8 devices tracks the
    # single-device run ----------------------------------------------------
    r8 = Embedding(sharded, mesh=mesh).fit(Y2).result_
    assert r8.energies[-1] < r8.energies[0]
    assert r8.X.shape == (Y2.shape[0], 2)
    # identical seeds: trajectories agree up to accumulated fp noise
    np.testing.assert_allclose(r8.energies, r1.energies, rtol=5e-3)

    # -- acceptance: normalized-model trainer parity, 8 devices vs 1 -------
    spec_t = spec.replace(kind="tsne", lam=1.0, max_iters=8)
    rt1 = Embedding(spec_t, mesh=mesh1).fit(Y2).result_
    rt8 = Embedding(spec_t.replace(backend="sparse-sharded"),
                    mesh=mesh).fit(Y2).result_
    assert rt8.energies[-1] < rt8.energies[0]
    np.testing.assert_allclose(rt8.energies, rt1.energies, rtol=5e-3)

    # -- mesh shapes the sparse path can't use are rejected ----------------
    mesh24 = jax.make_mesh((2, 4), ("data", "model"), **axis_types_kwargs(2))
    try:
        Embedding(sharded, mesh=mesh24).fit(Y2)
        raise SystemExit("expected ValueError for (2, 4) mesh")
    except ValueError as e:
        assert "size 1" in str(e), e
    print("SUBPROCESS_OK")
""")


def test_multi_device_sharded_sparse():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout


# the local-rows Pallas kernel (kernels/ops.ell_lap_matvec_local) inside
# shard_map bodies: energy/grad + SD-operator parity against the jnp
# per-shard gather on a real 8-device mesh, f32 exact and bf16 within
# storage-rounding distance
_KERNEL_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.launch.mesh import axis_types_kwargs
    from repro.kernels import ops
    from repro.sparse import (make_sharded_energy_grad,
                              make_sharded_sd_operator,
                              shard_sparse_affinities, sparse_affinities)
    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((8, 1), ("data", "model"), **axis_types_kwargs(2))

    n = 50                    # ragged: exercises row + sublane padding
    Y = jax.random.normal(jax.random.PRNGKey(0), (n, 6))
    X = jax.random.normal(jax.random.PRNGKey(1), (n, 2)) * 0.5
    key = jax.random.PRNGKey(7)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(a) + 1e-30))

    for kind, lam in [("ee", 50.0), ("tsne", 2.0)]:
        saff = sparse_affinities(Y, k=10, perplexity=3.0, model=kind)
        sg = shard_sparse_affinities(mesh, ("data",), saff)
        eg_j, _ = make_sharded_energy_grad(mesh, ("data",), sg, kind,
                                           n_negatives=5)
        eg_k, _ = make_sharded_energy_grad(mesh, ("data",), sg, kind,
                                           n_negatives=5,
                                           kernel_impl="pallas-interpret")
        disp = ops.last_dispatch("ell_lap_matvec_local")
        assert disp["path"] == "pallas" and disp["reason"] == "forced-on", \\
            disp
        if kind == "tsne":
            E1, G1, z1 = eg_j(X, lam, key, jnp.zeros(()))
            E2, G2, z2 = eg_k(X, lam, key, jnp.zeros(()))
            assert abs(float(z1 - z2)) / abs(float(z1)) < 1e-5
        else:
            E1, G1 = eg_j(X, lam, key)
            E2, G2 = eg_k(X, lam, key)
        relE = abs(float(E1 - E2)) / abs(float(E1))
        relG = rel(G1, G2)
        assert relE < 1e-5 and relG < 1e-5, (kind, relE, relG)

        # bf16 storage: within bf16 rounding of the f32 path
        eg_b, _ = make_sharded_energy_grad(mesh, ("data",), sg, kind,
                                           n_negatives=5,
                                           kernel_impl="pallas-interpret",
                                           kernel_precision="bfloat16")
        out_b = eg_b(X, lam, key) if kind != "tsne" else \\
            eg_b(X, lam, key, jnp.zeros(()))
        relGb = rel(G1, out_b[1])
        assert relGb < 5e-2, (kind, relGb)

    # SD operator through the kernel
    saff = sparse_affinities(Y, k=10, perplexity=3.0, model="ee")
    sg = shard_sparse_affinities(mesh, ("data",), saff)
    mv1, d1, mu1 = make_sharded_sd_operator(mesh, ("data",), sg, saff,
                                            1e-5)
    mv2, d2, mu2 = make_sharded_sd_operator(mesh, ("data",), sg, saff,
                                            1e-5,
                                            kernel_impl="pallas-interpret")
    V = jax.random.normal(jax.random.PRNGKey(3), (n, 2))
    r = rel(mv1(V), mv2(V))
    assert r < 1e-5, r
    print("SUBPROCESS_OK")
""")


def test_multi_device_sharded_kernel_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _KERNEL_SUBPROCESS_PROG],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout


# -- in-process checks on the (1, 1) mesh ---------------------------------------


def _problem(n=41, d_hi=6, seed=0):
    Y = jax.random.normal(jax.random.PRNGKey(seed), (n, d_hi))
    X = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, 2)) * 0.5
    return Y, X


def test_sharded_eg_single_device_parity():
    """shard_map with one shard must reproduce energy_and_grad_sparse."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    Y, X = _problem()
    saff = sparse_affinities(Y, k=10, perplexity=3.0, model="ee")
    sg = shard_sparse_affinities(mesh, ("data",), saff)
    eg, e_only = make_sharded_energy_grad(mesh, ("data",), sg, "ee",
                                          n_negatives=6)
    key = jax.random.PRNGKey(2)
    E1, G1 = energy_and_grad_sparse(X, saff, "ee", 50.0, n_negatives=6,
                                    key=key)
    E2, G2 = eg(X, 50.0, key)
    np.testing.assert_allclose(float(E1), float(E2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(G1), np.asarray(G2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(e_only(X, 50.0, key)), float(E1),
                               rtol=1e-6)


def test_sharded_operator_single_device_parity():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    Y, X = _problem()
    saff = sparse_affinities(Y, k=10, perplexity=3.0, model="ee")
    sg = shard_sparse_affinities(mesh, ("data",), saff)
    mv1, d1, _ = make_sd_operator(saff.graph, saff.rev, 1e-5)
    mv2, d2, _ = make_sharded_sd_operator(mesh, ("data",), sg, saff, 1e-5)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_allclose(np.asarray(mv1(X)), np.asarray(mv2(X)),
                               rtol=1e-5, atol=1e-6)


def test_validate_sparse_mesh_messages():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    validate_sparse_mesh(mesh, ("data",))          # size-1 col axis: fine
    with pytest.raises(ValueError, match="not in mesh"):
        validate_sparse_mesh(mesh, ("nope",))


def test_normalized_sharded_single_device_parity():
    """Normalized kinds build and match energy_and_grad_sparse on a (1, 1)
    mesh, including the threaded partition-function estimate (the former
    build-time rejection is lifted)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    Y, X = _problem()
    saff = sparse_affinities(Y, k=10, perplexity=3.0, model="tsne")
    sg = shard_sparse_affinities(mesh, ("data",), saff)
    eg, e_only = make_sharded_energy_grad(mesh, ("data",), sg, "tsne",
                                          n_negatives=6)
    key = jax.random.PRNGKey(2)
    E1, G1, z1 = energy_and_grad_sparse(X, saff, "tsne", 2.0, n_negatives=6,
                                        key=key, return_state=True)
    E2, G2, z2 = eg(X, 2.0, key, jnp.zeros(()))
    np.testing.assert_allclose(float(E1), float(E2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(G1), np.asarray(G2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(z1), float(z2), rtol=1e-6)
    np.testing.assert_allclose(float(e_only(X, 2.0, key)), float(E1),
                               rtol=1e-6)
