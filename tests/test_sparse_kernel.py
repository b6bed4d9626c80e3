"""Pallas sparse attractive kernel vs the jnp ELL oracle (interpret mode on
CPU, same caveat as test_kernels_pairwise: validates tiling/padding/gather
logic, not Mosaic codegen)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import ell_lap_matvec_ref
from repro.sparse import make_sd_operator, reverse_graph, sparse_affinities


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


# -- per-row live lengths: trailing zero-weight slots are skipped --------------


def _ragged_reverse_graph():
    """The reverse of a calibrated k-NN graph: uneven in-degrees make its
    rows ragged, padded with zero-weight self slots up to the widest.
    Row 0 loses every weight (live length 0), and a weight goes from inside
    the live range of the first row with three or more slots."""
    Y = jax.random.normal(jax.random.PRNGKey(13), (70, 6))
    rev = reverse_graph(sparse_affinities(Y, k=5, perplexity=3.0,
                                          model="ee").graph)
    w = np.array(rev.weights)
    w[0] = 0.0
    live = np.asarray(ops.ell_live_lengths(jnp.asarray(w)))
    w[int(np.argmax(live >= 3)), 1] = 0.0
    w = jnp.asarray(w)
    X = jax.random.normal(jax.random.PRNGKey(12), (70, 3))
    return X, rev.indices, w


def test_ragged_graph_covers_every_length_case():
    X, idx, w = _ragged_reverse_graph()
    n, k = w.shape
    live = np.asarray(ops.ell_live_lengths(w))
    assert k % 8 and live.min() == 0 and live.max() == k
    assert np.any(live % 8 != 0) and np.any(live < k)
    inner = (np.asarray(w) == 0) & (np.arange(k) < live[:, None])
    assert inner.any()                        # a zero inside a live range
    assert n % 16                             # ops pads rows beyond N


@pytest.mark.parametrize("layout", ["vmem", "hbm"])
@pytest.mark.parametrize("given", [False, True])
def test_live_lengths_bit_equal_to_full_width(layout, given):
    """Cutting rows at their live length changes no bit of the product:
    each skipped slot would add exactly 0 * x.  The full-width loop is
    the same kernel with every row's length k."""
    X, idx, w = _ragged_reverse_graph()
    n, k = w.shape
    kw = dict(impl="pallas-interpret", layout=layout, block_rows=16,
              chunk=4, lane=8)
    full = ops.ell_lap_matvec(X, idx, w, lengths=jnp.full(n, k), **kw)
    live = ops.ell_lap_matvec(
        X, idx, w, lengths=ops.ell_live_lengths(w) if given else None, **kw)
    np.testing.assert_array_equal(np.asarray(live), np.asarray(full))
    np.testing.assert_allclose(np.asarray(live),
                               np.asarray(ell_lap_matvec_ref(X, idx, w)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("row0", [0, 16, 48])
def test_live_lengths_bit_equal_local_rows(row0):
    X, idx, w = _ragged_reverse_graph()
    k = w.shape[1]
    nb = 16

    @jax.jit
    def f(r0, idx_l, w_l, len_l):
        return ops.ell_lap_matvec_local(X, idx_l, w_l, r0, block_rows=8,
                                        interpret=True, storage="float32",
                                        lane=8, lengths=len_l)

    rows = slice(row0, row0 + nb)
    live = f(jnp.int32(row0), idx[rows], w[rows], None)
    full = f(jnp.int32(row0), idx[rows], w[rows], jnp.full(nb, k))
    np.testing.assert_array_equal(np.asarray(live), np.asarray(full))


def test_live_lengths_definition():
    w = jnp.array([[0.0, 0.0, 0.0, 0.0],
                   [1.0, 0.0, 0.0, 0.0],
                   [0.0, 0.0, 2.0, 0.0],
                   [1.0, 0.0, 1.0, -0.0],
                   [0.5, 0.5, 0.5, 0.5],
                   [0.0, jnp.nan, 0.0, 0.0],
                   [0.0, 0.0, 0.0, -1e-38]])
    np.testing.assert_array_equal(np.asarray(ops.ell_live_lengths(w)),
                                  [0, 1, 3, 3, 4, 2, 4])
    assert ops.ell_live_lengths(w).dtype == jnp.int32


def test_dispatch_records_row_cut():
    X, idx, w = _rand_graph(13, 32, 6, 2)
    kw = dict(impl="pallas-interpret", block_rows=16, lane=8)
    ops.ell_lap_matvec(X, idx, w, **kw)
    disp = ops.last_dispatch("ell_lap_matvec")
    assert disp["row_cut"] == "live-length" and disp["lengths"] == "derived"
    ops.ell_lap_matvec(X, idx, w, lengths=ops.ell_live_lengths(w), **kw)
    assert ops.last_dispatch("ell_lap_matvec")["lengths"] == "given"
    # the jnp oracle runs every slot and says nothing of a cut
    ops.ell_lap_matvec(X, idx, w, impl="jnp")
    assert "row_cut" not in ops.last_dispatch("ell_lap_matvec")


def test_sd_operator_hands_its_lengths_to_the_kernel():
    """make_sd_operator computes the live lengths once, beside the
    degrees; every product it runs is handed them."""
    Y = jax.random.normal(jax.random.PRNGKey(14), (40, 5))
    saff = sparse_affinities(Y, k=4, perplexity=3.0, model="ee")
    rev = reverse_graph(saff.graph)
    matvec, _, _ = make_sd_operator(saff.graph, rev,
                                    impl="pallas-interpret", lane=8)
    V = jax.random.normal(jax.random.PRNGKey(15), (40, 2))
    out = matvec(V)
    assert ops.last_dispatch("ell_lap_matvec")["lengths"] == "given"
    ref_mv, _, _ = make_sd_operator(saff.graph, rev, impl="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_mv(V)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [150, 675])
def test_smem_rows_holds_index_weight_and_length_tiles(k):
    # the mnist20k widths: k = 3 * perplexity and the reverse graph's
    rows = ops.smem_rows(k)
    assert rows % 8 == 0
    assert ops.smem_tile_bytes(rows, k) <= ops._SMEM_TILE_BUDGET
    assert ops.smem_tile_bytes(rows + 8, k) > ops._SMEM_TILE_BUDGET
    # the length tile is counted: without it the same rows would leave room
    assert ops.smem_tile_bytes(rows, k) > 2 * 2 * rows * 4 * (-(-k // 128)
                                                              * 128)
