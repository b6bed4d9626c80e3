"""Fused pairwise embedding kernel (Pallas TPU).

Computes, in one streaming pass over (row-tile, col-tile) blocks of the
virtual N x N interaction matrix, the four quantities of the unified
contract in ref.py:  la_x = L(a)X, lb_x = L(b)X, e_plus, s.

TPU adaptation of the paper's O(N^2 d) bottleneck (DESIGN.md §3.1):
  * the pairwise squared-distance tile is one MXU matmul
    (t = |xi|^2 + |xj|^2 - 2 xi xj^T),
  * kernel evaluation + weighting runs on the VPU,
  * row-block accumulators (la_x, lb_x) live in VMEM across the column-tile
    sweep (output BlockSpec maps every j to the same row block),
  * scalar accumulators (e_plus, s) persist across the whole grid in one
    lane-dense (8, 128) VMEM block each, every element holding the value
    (the TPU cannot store a lone scalar to VMEM; ops read element [0, 0]),
  * the N x N matrix is never materialized in HBM.

Grid iteration order on TPU is sequential with the last axis minor, which is
what makes the revisited-output-block accumulation pattern legal.

The embedding dimension d is tiny (2-3 in the paper); callers (ops.py) pad it
to the lane width so every tile is hardware-aligned, and pad N to a tile
multiple with zero rows (zero weights => padded rows contribute exactly
nothing; see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import KINDS, PairwiseTerms

# one f32 vreg: the smallest block a vector value can be stored to
_SCALAR_BLOCK = (8, 128)


def _tile_terms(kind: str, t, wa, wb, xi, xj):
    """Per-tile a/b weights and scalar contributions. All (TR, TC) f32."""
    if kind in ("ee", "ssne"):
        a = wa
        b = wb * jnp.exp(-t)
        ep = jnp.sum(wa * t)
        s = jnp.sum(b)
    elif kind == "tsne":
        K = 1.0 / (1.0 + t)
        a = wa * K
        b = wb * (K * K)
        ep = jnp.sum(wa * jnp.log1p(t))
        s = jnp.sum(wb * K)
    elif kind == "tee":
        K = 1.0 / (1.0 + t)
        a = wa
        b = wb * (K * K)
        ep = jnp.sum(wa * t)
        s = jnp.sum(wb * K)
    elif kind == "epan":
        supp = (t < 1.0).astype(t.dtype)
        a = wa
        b = wb * supp
        ep = jnp.sum(wa * t)
        s = jnp.sum(wb * jnp.maximum(1.0 - t, 0.0))
    else:  # pragma: no cover - guarded by ops.py
        raise ValueError(kind)
    return a, b, ep, s


def _pairwise_kernel(x_row_ref, x_col_ref, wa_ref, wb_ref,
                     la_ref, lb_ref, ep_ref, s_ref, *, kind: str):
    i = pl.program_id(0)
    j = pl.program_id(1)

    xi = x_row_ref[...].astype(jnp.float32)   # (TR, dp)
    xj = x_col_ref[...].astype(jnp.float32)   # (TC, dp)
    wa = wa_ref[...].astype(jnp.float32)      # (TR, TC)
    wb = wb_ref[...].astype(jnp.float32)

    ri = jnp.sum(xi * xi, axis=-1, keepdims=True)            # (TR, 1)
    rj = jnp.sum(xj * xj, axis=-1, keepdims=True)            # (TC, 1)
    g = jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                         # (TR, TC) MXU
    t = jnp.maximum(ri + rj.T - 2.0 * g, 0.0)

    a, b, ep_tile, s_tile = _tile_terms(kind, t, wa, wb, xi, xj)

    # Laplacian-product row-tile contributions:
    #   (L(a) X)_i over this column tile = rowsum(a)*xi - a @ xj
    la_tile = jnp.sum(a, axis=1, keepdims=True) * xi - jax.lax.dot_general(
        a, xj, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    lb_tile = jnp.sum(b, axis=1, keepdims=True) * xi - jax.lax.dot_general(
        b, xj, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init_rows():
        la_ref[...] = jnp.zeros_like(la_ref)
        lb_ref[...] = jnp.zeros_like(lb_ref)

    la_ref[...] += la_tile
    lb_ref[...] += lb_tile

    @pl.when((i == 0) & (j == 0))
    def _init_scalars():
        ep_ref[...] = jnp.zeros_like(ep_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    ep_ref[...] += ep_tile
    s_ref[...] += s_tile


def pairwise_terms_pallas(
    X: jnp.ndarray,
    Wa: jnp.ndarray,
    Wb: jnp.ndarray,
    kind: str,
    *,
    block_rows: int = 256,
    block_cols: int = 256,
    interpret: bool = False,
) -> PairwiseTerms:
    """Pallas implementation of ref.pairwise_terms_ref.

    Requires N % block_rows == N % block_cols == 0 and the last dim of X
    padded to the lane width — ops.py handles both paddings.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}")
    n, dp = X.shape
    assert n % block_rows == 0 and n % block_cols == 0, (n, block_rows, block_cols)
    grid = (n // block_rows, n // block_cols)

    kernel = functools.partial(_pairwise_kernel, kind=kind)
    la, lb, ep, s = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((block_cols, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, dp), lambda i, j: (i, 0)),
            pl.BlockSpec(_SCALAR_BLOCK, lambda i, j: (0, 0)),
            pl.BlockSpec(_SCALAR_BLOCK, lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, dp), jnp.float32),
            jax.ShapeDtypeStruct((n, dp), jnp.float32),
            jax.ShapeDtypeStruct(_SCALAR_BLOCK, jnp.float32),
            jax.ShapeDtypeStruct(_SCALAR_BLOCK, jnp.float32),
        ],
        interpret=interpret,
    )(X, X, Wa, Wb)
    return PairwiseTerms(la_x=la, lb_x=lb, e_plus=ep[0, 0], s=s[0, 0])
