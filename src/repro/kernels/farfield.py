"""Barnes-Hut cell-interaction kernel (Pallas TPU).

Computes, per row tile, the far-field repulsion contract of
`kernels/ref.py::bh_interaction_ref`: each row n gathers W targets
(cell centers-of-mass, near-field points, or residual-group COMs —
sparse/farfield.py decides which) from a resident table and accumulates

    s_n = sum_j w_nj * sp(t_nj)            (the partition-function share)
    F_n = sum_j w_nj * b(t_nj) (x_n - c_j) (the repulsive Laplacian row)

with (sp, b) = negative_pair_terms(kind, t) and t the squared distance to
the target.  Layout and conventions mirror the ELL gather kernel
(sparse_attractive.py):

  * grid over row tiles; the idx/w tiles ride SMEM (scalar reads), the
    x-row tile streams through VMEM, and the target table is resident
    whole and unpipelined in VMEM — tables are cell-aggregate grids
    (4^level rows) or, for the near-field listed pairs, X itself; ops.py
    falls back to the jnp oracle when the table exceeds the VMEM budget,
  * each target is one dynamic-offset row read `tab[pl.ds(i, 1), :]`
    addressed by a scalar index from SMEM (the TPU compiler refuses an
    in-kernel vector gather), and the per-target math runs on that row:
    t = |x_n - c|^2 by a lane reduction, then (sp, b), then the
    weighted accumulation — the same recipe as the oracle,
  * tables may be stored bf16-rounded; rows are read as f32 and every
    sum accumulates in f32, outputs are always f32,
  * d is pre-padded to the lane width and N to the tile size by ops.py;
    padding rows carry w = 0, which contributes exactly zero (the same
    masking invariant that covers rejected cells, empty cells and self
    pairs — see bh_interaction_ref).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import negative_pair_terms


def _bh_kernel(idx_ref, w_ref, x_row_ref, tab_ref, s_ref, f_ref, *, kind):
    width = idx_ref.shape[1]
    dp = f_ref.shape[-1]

    def row(r, carry):
        x = x_row_ref[pl.ds(r, 1), :].astype(jnp.float32)     # (1, dp)

        def target(j, acc):
            s_acc, f_acc = acc
            wj = w_ref[r, j]
            diff = x - tab_ref[pl.ds(idx_ref[r, j], 1), :].astype(
                jnp.float32)
            t = jnp.sum(diff * diff, axis=-1, keepdims=True)  # (1, 1)
            sp, b = negative_pair_terms(kind, t)
            return s_acc + wj * sp, f_acc + (wj * b) * diff

        s_acc, f_acc = jax.lax.fori_loop(
            0, width, target,
            (jnp.zeros((1, 1), jnp.float32), jnp.zeros((1, dp), jnp.float32)))
        f_ref[pl.ds(r, 1), :] = f_acc
        s_ref[pl.ds(r, 1), :] = jnp.broadcast_to(s_acc, (1, s_ref.shape[-1]))
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[0], row, 0)


def bh_interaction_pallas(
    X: jnp.ndarray,          # (N, dp) — dp lane-padded by ops.py
    idx: jnp.ndarray,        # (N, W) int32, in-range rows of `table`
    w: jnp.ndarray,          # (N, W)
    table: jnp.ndarray,      # (M, dp) — resident whole in VMEM
    kind: str,
    *,
    block_rows: int = 256,
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas implementation of ref.bh_interaction_ref, vmem layout.

    Requires N % block_rows == 0 (ops.py pads with w = 0 rows) and both
    X and table lane-padded.  Returns (s (N,), F (N, dp)) in f32; the s
    output rides a (N, 128) lane-padded buffer, column 0 is the value."""
    n, dp = X.shape
    assert n % block_rows == 0, (n, block_rows)
    width = idx.shape[1]

    s, f = pl.pallas_call(
        functools.partial(_bh_kernel, kind=kind),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, width), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, width), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, 128), jnp.float32),
            jax.ShapeDtypeStruct((n, dp), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(idx, w, X, table)
    return s[:, 0], f
