"""The main-path Pallas kernels compile for a TPU v5e chip.

Each case lowers and compiles a kernel at the widths the paper's
configurations run (`configs/embedding_paper.py`) for a DESCRIBED v5e —
the TPU compiler is installed, no chip is attached, nothing runs.  This
catches what interpret mode cannot: an in-kernel op Mosaic does not
lower, an SMEM or VMEM tile the chip cannot hold.  Tile choices come from
the same `kernels.ops` helpers the dispatcher uses.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops
from repro.kernels.sparse_attractive import ell_lap_matvec_local_pallas

N_MNIST, K_MNIST = 20_000, 150    # embedding-mnist20k: perplexity 50, k = 3p
N_COIL = 720                      # embedding-coil20
LANE = 128
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ell_args(n, k, sharding):
    return (_sds((n, 2), F32, sharding), _sds((n, k), I32, sharding),
            _sds((n, k), F32, sharding))


def _compile_ell(n, k, layout, sharding):
    br = min(ops.legal_tile(256, n, 8), ops.smem_rows(k))
    chunk = (autotune.fit_divisor(br, min(8, ops.hbm_max_chunk(k, LANE)))
             if layout == "hbm" else 0)
    return ops._ell_pallas.lower(
        *_ell_args(n, k, sharding), block_rows=br, layout=layout,
        chunk=chunk, interpret=False, lane=LANE, storage="float32",
        vlimit=ops.vmem_limit()).compile()


def test_ell_vmem_layout_mnist20k(one_chip):
    # resident X at N = 20000 is 10.24 MB f32 at the 128-lane padding:
    # inside the default VMEM budget, so auto dispatch picks this layout
    assert ops._ell_decide(N_MNIST, K_MNIST, 2, "pallas", False, None,
                           "float32", LANE)[2] == "vmem"
    assert _compile_ell(N_MNIST, K_MNIST, "vmem", one_chip) is not None


@pytest.mark.parametrize("k", [K_MNIST, 675])
def test_ell_hbm_layout_mnist20k(one_chip, k):
    # k = 675: the reverse graph's width (its maximum in-degree) on the
    # seed-0 mnist_like data at perplexity 50 — the SMEM tiles and the DMA
    # double buffer shrink the row tile and the chunk there
    assert _compile_ell(N_MNIST, k, "hbm", one_chip) is not None


def test_ell_local_rows_four_way_shard(one_chip):
    nb = -(-N_MNIST // 4)                 # one shard of a (4, 1) mesh
    br = autotune.fit_divisor(nb, ops.smem_rows(K_MNIST), 8)
    fn = jax.jit(lambda X, i, w, r0: ell_lap_matvec_local_pallas(
        X, i, w, r0, block_rows=br, vmem_limit_bytes=ops.vmem_limit()))
    compiled = fn.lower(_sds((4 * nb, LANE), F32, one_chip),
                        _sds((nb, K_MNIST), I32, one_chip),
                        _sds((nb, K_MNIST), F32, one_chip),
                        _sds((), I32, one_chip)).compile()
    assert compiled is not None


@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_pairwise_coil20(one_chip, storage):
    n = N_COIL
    compiled = ops._pairwise_pallas.lower(
        _sds((n, 2), F32, one_chip), _sds((n, n), F32, one_chip),
        _sds((n, n), F32, one_chip), kind="ee", block_rows=256,
        block_cols=256, interpret=False, lane=LANE,
        storage=storage).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the interaction batches the N = 20000 tree builds (make_grid_plan's
# auto depth 7, r = 2): far-field levels against 4^l-row COM tables, the
# near field's 400 listed slots in 128-wide chunks against X itself
@pytest.mark.parametrize("width,table_rows", [(96, 4 ** 7), (128, N_MNIST)])
def test_bh_interaction_tree_mnist20k(one_chip, width, table_rows):
    n = N_MNIST
    compiled = ops._bh_pallas.lower(
        _sds((n, 2), F32, one_chip), _sds((n, width), I32, one_chip),
        _sds((n, width), F32, one_chip), _sds((table_rows, 2), F32, one_chip),
        kind="tsne", block_rows=min(256, ops.smem_rows(width)),
        interpret=False, lane=LANE, storage="float32",
        vlimit=ops.vmem_limit()).compile()
    assert compiled is not None
