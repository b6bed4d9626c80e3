"""Sparse Laplacian operators over ELL graphs, and preconditioned CG.

All operators apply the SYMMETRIC weight matrix W = (A + A^T)/2 implicitly
from the directed ELL storage (graph.py):

    W X       = (A X + A^T X) / 2         gather  +  scatter-add
    deg(W)    = (out_degree + in_degree)/2
    L(W) X    = deg(W) * X - W X

The gather half (A X) is the Pallas-accelerated hot path
(kernels/sparse_attractive.py via kernels.ops.ell_lap_matvec); the
scatter-add half stays in XLA, whose scatter lowering is efficient and —
unlike the gather — has no fixed per-row arity to tile over.

The spectral-direction solve B p = -g with B = 4 L(W+) + mu I never forms
(N, N): `pcg` is Jacobi-preconditioned CG on the (N, d) right-hand side
(all d columns share B, so one matvec per iteration serves every column).
An incomplete-Cholesky preconditioner is a ROADMAP open item — Jacobi is
already a good match because B's diagonal 4 deg + mu dominates when the
calibrated row degrees are O(1).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .graph import NeighborGraph

Array = jnp.ndarray


def out_degree(g: NeighborGraph) -> Array:
    """Row sums of A (padded slots have zero weight)."""
    return jnp.sum(g.weights, axis=-1)


def in_degree(g: NeighborGraph) -> Array:
    """Column sums of A, by scatter-add."""
    d = jnp.zeros(g.n, dtype=g.weights.dtype)
    return d.at[g.indices].add(g.weights)


def sym_degree(g: NeighborGraph) -> Array:
    """Degrees of the implicit W = (A + A^T)/2."""
    return 0.5 * (out_degree(g) + in_degree(g))


def ell_matvec(g: NeighborGraph, X: Array) -> Array:
    """A @ X by row gather: sum_j w_nj * X[i_nj]."""
    return jnp.einsum("nk,nkd->nd", g.weights, X[g.indices])


def ell_t_matvec(g: NeighborGraph, X: Array) -> Array:
    """A^T @ X by scatter-add: row m accumulates w_nm * X[n]."""
    out = jnp.zeros_like(X)
    contrib = g.weights[:, :, None] * X[:, None, :]     # (N, k, d)
    return out.at[g.indices].add(contrib)


def sym_lap_matvec(g: NeighborGraph, X: Array,
                   rev: NeighborGraph | None = None,
                   lengths: tuple[Array, Array] | None = None,
                   **impl) -> Array:
    """L((A + A^T)/2) @ X in O(N k d), as (L(A)X + L(A^T)X) / 2.

    When `rev` (the precomputed transpose ELL, graph.reverse_graph) is
    given, BOTH halves are directed-Laplacian row gathers through the
    Pallas dispatcher (kernels.ops.ell_lap_matvec; `impl` kwargs are
    forwarded) — the form the CG hot loop needs, since XLA's CPU
    scatter-add is orders of magnitude slower than the gather.  Without
    `rev` the transpose half falls back to scatter-add — fine for graphs
    that change every iteration (sampled negatives) where building the
    transpose would itself cost a scatter.  `lengths`, the live lengths
    of `g` and `rev` (kernels.ops.ell_live_lengths), spare the kernels
    deriving them on every product.  The two halves run under the
    device scopes `laplacian/forward` and `laplacian/reverse`
    (docs/observability.md)."""
    g_len, rev_len = lengths if lengths is not None else (None, None)
    with jax.named_scope("laplacian/forward"):
        la_x = ops.ell_lap_matvec(X, g.indices, g.weights, lengths=g_len,
                                  **impl)
    if rev is not None:
        with jax.named_scope("laplacian/reverse"):
            lat_x = ops.ell_lap_matvec(X, rev.indices, rev.weights,
                                       lengths=rev_len, **impl)
    else:
        lat_x = in_degree(g)[:, None] * X - ell_t_matvec(g, X)
    return 0.5 * (la_x + lat_x)


def make_sd_operator(g: NeighborGraph, rev: NeighborGraph | None,
                     mu_scale: float = 1e-5, **impl):
    """(matvec, inv_diag, mu) for the sparse spectral-direction system
    B = 4 L((A + A^T)/2) + mu I — the one place the jitter formula and
    Jacobi diagonal live for the pure-sparse case (trainer, benchmarks).
    core.strategies.SparseSD generalizes this with the full-degree
    residual shift for dense-kappa conversions.  `impl` kwargs (e.g.
    ``impl="pallas"``, ``storage_dtype="bfloat16"``) are forwarded to the
    kernel dispatcher for every matvec — this is the CG hot path.  The
    rows' live lengths are computed here once, like the degrees, so the
    kernels skip each row's trailing zero-weight slots without a
    reduction per product."""
    bd = 4.0 * sym_degree(g)
    mu = jnp.maximum(1e-10 * jnp.min(bd), mu_scale * jnp.mean(bd))
    inv_diag = 1.0 / (bd + mu)
    lengths = (ops.ell_live_lengths(g.weights),
               None if rev is None else ops.ell_live_lengths(rev.weights))

    def matvec(V):
        return 4.0 * sym_lap_matvec(g, V, rev=rev, lengths=lengths,
                                    **impl) + mu * V

    return matvec, inv_diag, mu


def sym_matvec(g: NeighborGraph, X: Array,
               rev: NeighborGraph | None = None) -> Array:
    """W @ X for the implicit W = (A + A^T)/2.  With `rev` both halves are
    row gathers; without it the transpose half is a scatter-add."""
    ax = ell_matvec(g, X)
    atx = ell_matvec(rev, X) if rev is not None else ell_t_matvec(g, X)
    return 0.5 * (ax + atx)


@functools.partial(jax.jit, static_argnames=("d", "n_iters", "oversample"))
def sparse_laplacian_eigenmaps(g: NeighborGraph,
                               rev: NeighborGraph | None = None,
                               d: int = 2, n_iters: int = 300,
                               oversample: int = 6, seed: int = 0) -> Array:
    """Laplacian-eigenmaps init from ELL storage: O(N k d) per sweep, no
    (N, N) array — the sparse analogue of core.spectral_init.

    Same spectral problem as `laplacian_eigenmaps` (bottom nontrivial
    eigenvectors of the normalized Laplacian, i.e. TOP eigenvectors of
    M = D^{-1/2} W D^{-1/2}), solved by block subspace iteration on the
    shifted operator M + I (spectrum in [0, 2], so the algebraically
    largest eigenvalues are also largest in magnitude and the iteration
    cannot lock onto a negative tail mode), followed by a Rayleigh-Ritz
    projection to sort/clean the Ritz vectors.  The block carries
    `oversample` extra vectors so the wanted d+1 converge at the (much
    larger) gap to lambda_{d+1+oversample} instead of a possibly tiny
    lambda_{d+1} / lambda_{d+2} gap.  Matches the dense routine's gauge:
    drop the trivial top eigenvector, map back through D^{-1/2}, center,
    unit std per dimension."""
    with jax.named_scope("spectral-init"):
        n = g.n
        dg = jnp.maximum(sym_degree(g) if rev is None
                         else 0.5 * (out_degree(g) + out_degree(rev)), 1e-12)
        dinv = 1.0 / jnp.sqrt(dg)

        def Mv(V):
            return dinv[:, None] * sym_matvec(g, dinv[:, None] * V, rev=rev)

        V = jax.random.normal(jax.random.PRNGKey(seed),
                              (n, min(d + 1 + oversample, n)),
                              dtype=g.weights.dtype)
        V, _ = jnp.linalg.qr(V)

        def sweep(_, V):
            V, _ = jnp.linalg.qr(Mv(V) + V)
            return V

        V = jax.lax.fori_loop(0, n_iters, sweep, V)
        # Rayleigh-Ritz: order the converged subspace by eigenvalue of M
        T = V.T @ Mv(V)
        _, S = jnp.linalg.eigh(0.5 * (T + T.T))    # ascending
        U = V @ S[:, ::-1]                          # descending: col 0 trivial
        X = dinv[:, None] * U[:, 1:d + 1]
        X = X - jnp.mean(X, axis=0, keepdims=True)
        return X / jnp.maximum(jnp.std(X, axis=0, keepdims=True), 1e-12)


# -- preconditioned CG ----------------------------------------------------------


class PCGResult(NamedTuple):
    x: Array             # (N, d)
    n_iters: Array
    rel_residual: Array


def pcg(
    matvec: Callable[[Array], Array],
    B: Array,                 # (N, d) right-hand side
    x0: Array,                # (N, d) warm start
    inv_diag: Array | None = None,   # (N,) Jacobi preconditioner diag(M)^-1
    tol: float = 1e-2,
    maxiter: int = 100,
) -> PCGResult:
    """Preconditioned conjugate gradients on a multi-column RHS.

    All columns share the same SPD operator, so the d systems run fused:
    one operator application per iteration, scalar products summed over all
    columns (equivalent to CG on the block-diagonal system; exact for the
    Kronecker structure B (x) I_d of the spectral direction)."""
    precond = ((lambda r: inv_diag[:, None] * r) if inv_diag is not None
               else (lambda r: r))
    b_norm = jnp.maximum(jnp.linalg.norm(B), 1e-30)
    r0 = B - matvec(x0)
    z0 = precond(r0)
    rz0 = jnp.vdot(r0, z0)

    def cond(carry):
        _, r, _, _, k = carry
        return jnp.logical_and(jnp.linalg.norm(r) > tol * b_norm, k < maxiter)

    def body(carry):
        x, r, p, rz, k = carry
        Ap = matvec(p)
        alpha = rz / jnp.maximum(jnp.vdot(p, Ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * Ap
        z = precond(r)
        rz_new = jnp.vdot(r, z)
        beta = rz_new / jnp.maximum(rz, 1e-30)
        p = z + beta * p
        return x, r, p, rz_new, k + 1

    x, r, _, _, k = jax.lax.while_loop(
        cond, body, (x0, r0, z0, rz0, jnp.asarray(0)))
    return PCGResult(x=x, n_iters=k,
                     rel_residual=jnp.linalg.norm(r) / b_norm)
