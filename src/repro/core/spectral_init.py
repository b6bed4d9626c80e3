"""Spectral (Laplacian-eigenmaps) initialization — the lambda = 0 solution.

The paper's formulation reduces to Laplacian eigenmaps at lambda = 0 with
quadratic constraints; its solution (bottom nontrivial generalized
eigenvectors of (L+, D+)) is both the standard good initializer for the
nonconvex methods and the exact minimizer the SD Hessian corresponds to.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .laplacian import degree

Array = jnp.ndarray


def laplacian_eigenmaps(Wp: Array, d: int = 2) -> Array:
    """Bottom-d nontrivial eigenvectors of the normalized Laplacian.

    Solves L u = mu D u via the symmetric normalized form
    I - D^{-1/2} W D^{-1/2}; returns X = D^{-1/2} U (N, d), scaled to unit
    std per dimension (a conventional, shift/rotation-invariant gauge).
    """
    with jax.named_scope("spectral-init"):
        dg = jnp.maximum(degree(Wp), 1e-12)
        dinv = 1.0 / jnp.sqrt(dg)
        M = dinv[:, None] * Wp * dinv[None, :]
        # eigh of I - M has the same eigenvectors as M (reversed order);
        # use M and take the TOP d+1 eigenvectors (largest eigenvalues of
        # M = smallest of the Laplacian), dropping the trivial constant one.
        vals, vecs = jnp.linalg.eigh(0.5 * (M + M.T))
        U = vecs[:, -(d + 1):-1][:, ::-1]   # skip the top (trivial) one
        X = dinv[:, None] * U
        X = X - jnp.mean(X, axis=0, keepdims=True)
        X = X / jnp.maximum(jnp.std(X, axis=0, keepdims=True), 1e-12)
        return X
