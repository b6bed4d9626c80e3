"""Distributed embedding: multi-device correctness via a subprocess with 8
forced host devices (the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.api import Embedding, EmbedSpec
from tests.conftest import three_loops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import axis_types_kwargs
    from repro.core import make_affinities, energy_and_grad
    from repro.embed import (EmbedMeshSpec, make_distributed_energy_grad,
                             make_block_jacobi_setup, make_block_jacobi_solve,
                             shard_pairwise, shard_rows)
    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((2, 4), ("data", "model"), **axis_types_kwargs(2))
    spec = EmbedMeshSpec(row_axes=("data",), col_axis="model")

    N, d = 64, 2
    key = jax.random.PRNGKey(0)
    Y = jax.random.normal(key, (N, 8))
    X = jax.random.normal(jax.random.PRNGKey(1), (N, d)) * 0.5
    for kind, lam in [("ee", 50.0), ("tsne", 1.0)]:
        aff = make_affinities(Y, 10.0, model=kind)
        eg = make_distributed_energy_grad(mesh, spec, kind)
        Wp = shard_pairwise(mesh, spec, aff.Wp)
        Wm = shard_pairwise(mesh, spec, aff.Wm)
        E1, G1 = eg(X, Wp, Wm, lam)
        E2, G2 = energy_and_grad(X, aff, kind, lam)
        assert np.isclose(float(E1), float(E2), rtol=1e-4), (kind, float(E1), float(E2))
        rel = float(jnp.linalg.norm(G1 - G2) / jnp.linalg.norm(G2))
        assert rel < 1e-4, (kind, rel)

    # block-Jacobi diagonal blocks must equal the dense diagonal blocks
    aff = make_affinities(Y, 10.0, model="ee")
    Wp = shard_pairwise(mesh, spec, aff.Wp)
    R = make_block_jacobi_setup(mesh, spec)(Wp)
    Rg = np.asarray(jax.device_get(R))             # (N, N/2) stacked blocks
    from repro.core.laplacian import degree
    deg = np.asarray(degree(aff.Wp))
    Wnp = np.asarray(aff.Wp)
    nb = N // 2
    for blk in range(2):
        sl = slice(blk * nb, (blk + 1) * nb)
        B = 4.0 * (np.diag(deg[sl]) - Wnp[sl, sl])
        mu = max(1e-10 * np.diag(B).min(), 1e-5 * np.diag(B).mean())
        B = B + mu * np.eye(nb)
        R_expected = np.linalg.cholesky(B)
        np.testing.assert_allclose(Rg[sl], R_expected, rtol=1e-3, atol=1e-5)
    print("SUBPROCESS_OK")
""")


def test_multi_device_distributed_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout


def test_trainer_fit_single_device(tmp_path):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    Y = three_loops(n_per=16, loops=2, dim=8)
    spec = EmbedSpec(kind="ee", lam=50.0, perplexity=8.0, max_iters=20,
                     backend="dense-mesh",
                     checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=5)
    res = Embedding(spec, mesh=mesh).fit(Y).result_
    assert res.energies[-1] < res.energies[0]
    assert np.all(np.isfinite(res.energies))

    # restart resumes from the saved checkpoint
    res2 = Embedding(spec, mesh=mesh).fit(Y).result_
    assert res2.resumed_from is not None
