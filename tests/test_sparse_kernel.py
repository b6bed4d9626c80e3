"""Pallas sparse attractive kernel vs the jnp ELL oracle (interpret mode on
CPU, same caveat as test_kernels_pairwise: validates tiling/padding/gather
logic, not Mosaic codegen)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import ell_lap_matvec_ref
from repro.sparse import sparse_affinities


def _rand_graph(seed: int, n: int, k: int, d: int):
    ki, kw, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
    idx = jax.random.randint(ki, (n, k), 0, n, dtype=jnp.int32)
    w = jnp.abs(jax.random.normal(kw, (n, k)))
    X = jax.random.normal(kx, (n, d))
    return X, idx, w


@pytest.mark.parametrize("n,k,d,br", [
    (64, 8, 2, 16),
    (96, 5, 3, 32),
    (70, 8, 2, 16),    # ragged N -> zero-row padding path
    (33, 16, 5, 16),   # k > block structure, ragged N
])
def test_sparse_kernel_matches_oracle(n, k, d, br):
    X, idx, w = _rand_graph(0, n, k, d)
    r = ell_lap_matvec_ref(X, idx, w)
    p = ops.ell_lap_matvec(X, idx, w, use_pallas=True, interpret=True,
                           block_rows=br, lane=8)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(r), rtol=5e-5,
        atol=5e-5 * float(jnp.max(jnp.abs(r)) + 1))


def test_sparse_kernel_duplicate_columns_sum():
    n, d = 16, 2
    idx = jnp.tile(jnp.arange(n, dtype=jnp.int32)[::-1][:, None], (1, 4))
    w = jnp.ones((n, 4))
    X = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    r = ell_lap_matvec_ref(X, idx, w)
    p = ops.ell_lap_matvec(X, idx, w, use_pallas=True, interpret=True,
                           block_rows=8, lane=8)
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=1e-5,
                               atol=1e-5)


def test_sparse_kernel_padding_rows_zero():
    """ops.py pads N to the block multiple with zero-weight rows; outputs
    for real rows must be unaffected and the pad sliced off."""
    n, k, d = 19, 4, 2
    X, idx, w = _rand_graph(1, n, k, d)
    out = ops.ell_lap_matvec(X, idx, w, use_pallas=True, interpret=True,
                             block_rows=16, lane=8)
    assert out.shape == (n, d)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ell_lap_matvec_ref(X, idx, w)),
                               rtol=5e-5, atol=5e-5)


def test_sparse_kernel_on_calibrated_graph():
    Y = jax.random.normal(jax.random.PRNGKey(2), (48, 6))
    saff = sparse_affinities(Y, k=10, perplexity=5.0, model="ee")
    g = saff.graph
    X = jax.random.normal(jax.random.PRNGKey(3), (48, 2))
    r = ell_lap_matvec_ref(X, g.indices, g.weights)
    p = ops.ell_lap_matvec(X, g.indices, g.weights, use_pallas=True,
                           interpret=True, block_rows=16, lane=8)
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=5e-5,
                               atol=5e-6)


def test_dispatch_defaults_to_ref_on_cpu():
    X, idx, w = _rand_graph(4, 32, 6, 2)
    out = ops.ell_lap_matvec(X, idx, w)     # no pallas flags
    # jit fusion may reassociate the accumulation: allclose, not bitwise
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ell_lap_matvec_ref(X, idx, w)),
                               rtol=1e-5, atol=1e-6)


# -- HBM-resident double-buffered gather layout ---------------------------------


@pytest.mark.parametrize("n,k,d", [
    (64, 8, 2),
    (70, 8, 2),    # ragged N -> zero-row padding
    (33, 1, 3),    # k=1: single DMA per row
    (96, 5, 5),
])
def test_hbm_layout_matches_oracle(n, k, d):
    X, idx, w = _rand_graph(5, n, k, d)
    r = ell_lap_matvec_ref(X, idx, w)
    p = ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret",
                           layout="hbm", block_rows=16, chunk=4, lane=8)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(r), rtol=1e-5,
        atol=1e-5 * float(jnp.max(jnp.abs(r)) + 1))


def test_vmem_cap_forces_hbm_layout(monkeypatch):
    """Above the resident-X VMEM budget, auto layout must flip to the
    double-buffered HBM gather — the cap-lift acceptance path — and stay
    on the oracle."""
    monkeypatch.setenv(ops.VMEM_X_BUDGET_ENV, "1024")
    X, idx, w = _rand_graph(6, 40, 4, 2)   # resident 48*8*4 = 1536 B
    p = ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret",
                           block_rows=16, chunk=4, lane=8)
    disp = ops.last_dispatch("ell_lap_matvec")
    assert disp["layout"] == "hbm" and disp["reason"] == "vmem-cap"
    np.testing.assert_allclose(np.asarray(p),
                               np.asarray(ell_lap_matvec_ref(X, idx, w)),
                               rtol=1e-5, atol=1e-5)


# -- bfloat16 storage / f32 accumulation ----------------------------------------


def test_bf16_storage_matches_jnp_bf16_path():
    """The Pallas bf16-storage path and the jnp path quantize through the
    same bf16 rounding, so they agree to f32 accumulation noise — and both
    sit within bf16 distance of the f32 oracle."""
    X, idx, w = _rand_graph(7, 64, 6, 3)
    p = ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret",
                           block_rows=16, lane=8,
                           storage_dtype="bfloat16")
    j = ops.ell_lap_matvec(X, idx, w, impl="jnp",
                           storage_dtype="bfloat16")
    np.testing.assert_allclose(np.asarray(p), np.asarray(j),
                               rtol=1e-5, atol=1e-6)
    r = ell_lap_matvec_ref(X, idx, w)
    rel = float(jnp.linalg.norm(p - r) / (jnp.linalg.norm(r) + 1e-30))
    assert rel < 5e-2
    disp = ops.last_dispatch("ell_lap_matvec")
    assert disp["storage"] == "bfloat16"


def test_bf16_storage_hbm_layout():
    X, idx, w = _rand_graph(9, 48, 4, 2)
    p = ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret",
                           layout="hbm", block_rows=16, chunk=4, lane=8,
                           storage_dtype="bfloat16")
    r = ell_lap_matvec_ref(X, idx, w)
    rel = float(jnp.linalg.norm(p - r) / (jnp.linalg.norm(r) + 1e-30))
    assert rel < 5e-2


# -- shard_map local-rows kernel ------------------------------------------------


def test_local_rows_kernel_matches_oracle():
    """The scalar-prefetch translated kernel on a row slice must equal the
    same rows of the full oracle (row indices stay global)."""
    n, k, d = 64, 4, 3
    X, idx, w = _rand_graph(8, n, k, d)
    full = ell_lap_matvec_ref(X, idx, w)
    for row0, nb in [(0, 16), (32, 16), (48, 16)]:
        out = ops.ell_lap_matvec_local(
            X, idx[row0:row0 + nb], w[row0:row0 + nb], row0,
            block_rows=16, interpret=True, storage="float32", lane=8)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(full[row0:row0 + nb]),
            rtol=5e-5, atol=5e-5)


def test_local_rows_kernel_traced_row0():
    """row0 arrives as a traced value inside shard_map bodies — the
    kernel must accept it under jit."""
    n, k, d = 64, 4, 2
    X, idx, w = _rand_graph(10, n, k, d)
    full = ell_lap_matvec_ref(X, idx, w)

    @jax.jit
    def f(r0, idx_l, w_l):
        return ops.ell_lap_matvec_local(X, idx_l, w_l, r0, block_rows=16,
                                        interpret=True, storage="float32",
                                        lane=8)

    out = f(jnp.int32(16), idx[16:32], w[16:32])
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[16:32]),
                               rtol=5e-5, atol=5e-5)


def test_resolve_local_ell_dispatch():
    # auto on CPU routes to the jnp per-shard gather, transparently
    assert ops.resolve_local_ell(16, 4, 2, n_rep=32) is None
    assert ops.last_dispatch("ell_lap_matvec_local")["reason"] == "no-tpu"
    # forced interpret: block_rows must tile the shard exactly
    kw = ops.resolve_local_ell(24, 4, 2, n_rep=48, impl="pallas-interpret")
    assert kw is not None and 24 % kw["block_rows"] == 0
    assert ops.last_dispatch("ell_lap_matvec_local")["path"] == "pallas"
