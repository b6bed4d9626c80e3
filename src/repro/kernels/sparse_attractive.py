"""Sparse attractive-term kernels (Pallas TPU): directed ELL Laplacian matvec.

Computes, per row tile, the gather half of the sparse attractive product
(sparse/linalg.py):

    (L(A) X)_n = (sum_j w_nj) x_n - sum_j w_nj x_{i_nj}

for an ELL graph (indices (N, k), weights (N, k)).  The transpose half
(A^T X, a scatter) stays in XLA — scatter has no fixed per-row arity to
tile over, while the gather half is the regular-access hot path.

Three layouts of the same contract (ops.py picks one per dispatch, see
docs/kernels.md):

  * `ell_lap_matvec_pallas` — "vmem": X is additionally passed whole and
    unpipelined (one VMEM copy, not a double-buffered block), so neighbor
    rows are read straight from VMEM.  Used while X fits the VMEM budget
    (ops.vmem_x_budget).
  * `ell_lap_matvec_pallas_hbm` — "hbm": X stays in HBM
    (`memory_space=ANY`); the kernel DMAs each chunk of rows' neighbor
    rows into a double-buffered VMEM scratch, overlapping the next
    chunk's copies with the current chunk's compute.  Lifts the VMEM cap.
  * `ell_lap_matvec_local_pallas` — "vmem" over a REPLICATED X but only a
    LOCAL row range of the graph: the variant `shard_map` bodies call
    (sparse/sharding.py).  The global->local translation happens at the
    BlockSpec level via a scalar-prefetch row-block offset, so the kernel
    body is shared with the single-device vmem layout verbatim.

The gather (all three layouts): the index and weight tiles live in SMEM,
and each neighbor row is one dynamic-offset row read `x[pl.ds(i, 1), :]`
addressed by a scalar index — the form Mosaic lowers (a vector gather
over the sublane axis, `jnp.take`, is refused by the TPU compiler).  The
row sum runs in f32 with a scalar weight per read:

  * X, weights and output are f32: a row read at an odd row offset of a
    bf16 array does not lower, so bf16 storage (ops.py) rounds X and the
    weights through bf16 and hands the kernels the rounded values as f32
    — the same quantization as the jnp path,
  * embedding dim d is pre-padded to the lane width by ops.py; N is
    pre-padded to the tile size with zero-weight rows, which contribute
    exactly zero (the ELL padding invariant).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# neighbor reads unrolled per loop trip: enough straight-line work for the
# scheduler to overlap the scalar index loads with the vector FMAs
_UNROLL = 8


def _lap_rows(load_row, w_ref, x_row_ref, out_ref, r0, nrows):
    """out[r] = (sum_j w[r, j]) x[r] - sum_j w[r, j] load_row(r, j) for
    the tile rows r0 .. r0 + nrows; `w_ref` is an SMEM (TR, k) tile."""
    k = w_ref.shape[1]
    dp = out_ref.shape[-1]

    def row(r, carry):
        def nbr(j, acc_deg):
            acc, deg = acc_deg
            wj = w_ref[r, j]
            return acc + wj * load_row(r, j).astype(jnp.float32), deg + wj

        def nbr_group(g, acc_deg):
            # Mosaic lowers only full or no fori_loop unrolling, so the
            # group of _UNROLL reads is unrolled by hand
            for u in range(_UNROLL):
                acc_deg = nbr(g * _UNROLL + u, acc_deg)
            return acc_deg

        acc_deg = (jnp.zeros((1, dp), jnp.float32), jnp.float32(0.0))
        acc_deg = jax.lax.fori_loop(0, k // _UNROLL, nbr_group, acc_deg)
        for j in range(k - k % _UNROLL, k):
            acc_deg = nbr(j, acc_deg)
        acc, deg = acc_deg
        xi = x_row_ref[pl.ds(r, 1), :].astype(jnp.float32)
        out_ref[pl.ds(r, 1), :] = deg * xi - acc
        return carry

    jax.lax.fori_loop(r0, r0 + nrows, row, 0)


def _ell_kernel(idx_ref, w_ref, x_row_ref, x_all_ref, out_ref):
    _lap_rows(lambda r, j: x_all_ref[pl.ds(idx_ref[r, j], 1), :],
              w_ref, x_row_ref, out_ref, 0, idx_ref.shape[0])


def _smem_tile(block_rows, k, index_map):
    return pl.BlockSpec((block_rows, k), index_map,
                        memory_space=pltpu.SMEM)


def ell_lap_matvec_pallas(
    X: jnp.ndarray,          # (N, dp) — dp lane-padded by ops.py
    indices: jnp.ndarray,    # (N, k) int32
    weights: jnp.ndarray,    # (N, k) f32
    *,
    block_rows: int = 256,
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas implementation of ref.ell_lap_matvec_ref, vmem layout.

    Requires N % block_rows == 0 (ops.py pads with zero-weight rows) and
    X's last dim lane-padded."""
    n, dp = X.shape
    assert n % block_rows == 0, (n, block_rows)
    k = indices.shape[1]

    return pl.pallas_call(
        _ell_kernel,
        grid=(n // block_rows,),
        in_specs=[
            _smem_tile(block_rows, k, lambda i: (i, 0)),
            _smem_tile(block_rows, k, lambda i: (i, 0)),
            pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(indices, weights, X, X)


def _ell_hbm_kernel(idx_ref, w_ref, x_row_ref, x_hbm_ref, out_ref, *,
                    chunk: int):
    """Double-buffered HBM gather: while chunk c's neighbor rows are being
    reduced, chunk c+1's rows are already in flight into the other buffer
    slot.  One DMA semaphore per slot: every row copy of a chunk signals
    it, and the wait loop consumes one row's worth per copy."""
    tr, k = idx_ref.shape
    n_chunks = tr // chunk
    per_chunk = chunk * k

    def scoped(buf, sems):
        # the descriptor for (slot, chunk, slot-row t) is rebuilt
        # identically at start() and wait() — the Pallas async-copy
        # contract
        def copy(slot, c, t):
            src = idx_ref[c * chunk + t // k, t % k]
            return pltpu.make_async_copy(
                x_hbm_ref.at[pl.ds(src, 1)], buf.at[slot, pl.ds(t, 1)],
                sems.at[slot])

        def start(slot, c):
            def body(t, carry):
                copy(slot, c, t).start()
                return carry
            jax.lax.fori_loop(0, per_chunk, body, 0)

        def wait(slot, c):
            def body(t, carry):
                copy(slot, c, t).wait()
                return carry
            jax.lax.fori_loop(0, per_chunk, body, 0)

        start(0, 0)

        def step(c, carry):
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                start(1 - slot, c + 1)

            wait(slot, c)
            r0 = c * chunk
            _lap_rows(
                lambda r, j: buf[slot, pl.ds((r - r0) * k + j, 1), :],
                w_ref, x_row_ref, out_ref, r0, chunk)
            return carry

        jax.lax.fori_loop(0, n_chunks, step, 0)

    pl.run_scoped(
        scoped,
        buf=pltpu.VMEM((2, per_chunk, x_hbm_ref.shape[-1]),
                       x_hbm_ref.dtype),
        sems=pltpu.SemaphoreType.DMA((2,)),
    )


def ell_lap_matvec_pallas_hbm(
    X: jnp.ndarray,          # (N, dp) — stays in HBM
    indices: jnp.ndarray,    # (N, k) int32
    weights: jnp.ndarray,    # (N, k) f32
    *,
    block_rows: int = 256,
    chunk: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """HBM-resident layout: same contract as `ell_lap_matvec_pallas`, but
    X never enters VMEM whole — per chunk of `chunk` rows, the chunk*k
    neighbor rows are DMA'd into a (2, chunk*k, dp) double buffer.  VMEM
    use is O(block_rows * dp + chunk * k * dp), independent of N."""
    n, dp = X.shape
    assert n % block_rows == 0, (n, block_rows)
    assert block_rows % chunk == 0, (block_rows, chunk)
    k = indices.shape[1]

    return pl.pallas_call(
        functools.partial(_ell_hbm_kernel, chunk=chunk),
        grid=(n // block_rows,),
        in_specs=[
            _smem_tile(block_rows, k, lambda i: (i, 0)),
            _smem_tile(block_rows, k, lambda i: (i, 0)),
            pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dp), jnp.float32),
        interpret=interpret,
    )(indices, weights, X, X)


def _ell_local_kernel(s_ref, idx_ref, w_ref, x_row_ref, x_all_ref, out_ref):
    del s_ref  # consumed by the x_row index map only
    _ell_kernel(idx_ref, w_ref, x_row_ref, x_all_ref, out_ref)


def ell_lap_matvec_local_pallas(
    X_rep: jnp.ndarray,      # (n_rep, dp) — REPLICATED, lane-padded
    indices: jnp.ndarray,    # (nb, k) int32 — LOCAL graph rows, global ids
    weights: jnp.ndarray,    # (nb, k) f32
    row0,                    # global row offset of this shard (traced OK)
    *,
    block_rows: int = 256,
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Local rows of L(A) X inside a shard_map body: the graph arrays are
    this shard's nb rows, X is the full replicated array, and the output
    is the local (nb, dp) slab.

    The global->local index translation happens at the BlockSpec level:
    `row0` rides in as a scalar-prefetch argument, and the x_row index map
    offsets every grid step by `row0 / block_rows` — so the kernel body is
    `_ell_kernel` verbatim, and `row0 % block_rows == 0` is required
    (sparse/sharding.py sizes shards so block_rows divides nb)."""
    nb, k = indices.shape
    dp = X_rep.shape[1]
    assert nb % block_rows == 0, (nb, block_rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb // block_rows,),
        in_specs=[
            _smem_tile(block_rows, k, lambda i, s: (i, 0)),
            _smem_tile(block_rows, k, lambda i, s: (i, 0)),
            pl.BlockSpec((block_rows, dp), lambda i, s: (s[0] + i, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, dp), lambda i, s: (i, 0)),
    )
    block0 = (jnp.asarray(row0, jnp.int32) // block_rows).reshape(1)
    return pl.pallas_call(
        _ell_local_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, dp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(block0, indices, weights, X_rep, X_rep)
