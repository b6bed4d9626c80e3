"""Partial-Hessian search-direction strategies (paper §2).

Every strategy defines a pd matrix B_k and the direction p_k = -B_k^{-1} g_k.
The choices reproduce the paper's lineup:

  GD      B = I                              (gradient descent)
  FP      B = 4 D+ (x) I_d                   (diagonal fixed-point iteration)
  DiagH   B = max(diag(full Hessian), mu)    (diagonal of the Hessian)
  SD      B = 4 L+_kappa (x) I_d + mu I      (the spectral direction;
                                              Cholesky factor cached at init)
  SD-     B_i = 4 L+ + 8 [L^xx]_{ii}^psd     (adds repulsive curvature;
                                              inexact linear-CG solve)

The kappa knob sparsifies L+ through the k-NN graph exactly as in the paper:
kappa >= N-1 is the full spectral direction, kappa = 0 degenerates to FP.

Strategy objects are frozen (static under jit); per-run tensors (Cholesky
factor, warm starts) live in the `state` pytree returned by `init`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import jax.scipy.linalg as jsl

from .affinities import Affinities
from .cg import batched_cg
from .hessians import diag_hessian, xx_weights_ii
from .laplacian import degree, sparsified_attractive_matrix
from .objectives import attractive_weights

Array = jnp.ndarray
State = Any


def _jitter(Bdiag_min: Array, Bdiag_mean: Array) -> Array:
    """Paper's mu = 1e-10 min(L+_nn); we floor it relative to the mean degree
    for fp32 robustness (the paper ran double precision — DESIGN.md §7)."""
    return jnp.maximum(1e-10 * Bdiag_min, 1e-6 * Bdiag_mean)


@dataclasses.dataclass(frozen=True)
class GD:
    name: str = "GD"

    def init(self, X0, aff: Affinities, kind: str, lam) -> State:
        return ()

    def direction(self, state, X, G, aff, kind, lam):
        return -G, state


@dataclasses.dataclass(frozen=True)
class FP:
    """Diagonal fixed-point method: B = 4 D+ (Carreira-Perpinan 2010)."""

    name: str = "FP"

    def init(self, X0, aff: Affinities, kind: str, lam) -> State:
        dp = degree(attractive_weights(aff, kind))
        mu = _jitter(jnp.min(dp), jnp.mean(dp))
        return {"inv_diag": 1.0 / (4.0 * dp + mu)}

    def direction(self, state, X, G, aff, kind, lam):
        return -state["inv_diag"][:, None] * G, state


@dataclasses.dataclass(frozen=True)
class DiagH:
    """Diagonal of the full Hessian, clipped positive (recomputed each k)."""

    name: str = "DiagH"
    floor_scale: float = 1e-8

    def init(self, X0, aff: Affinities, kind: str, lam) -> State:
        return ()

    def direction(self, state, X, G, aff, kind, lam):
        d = diag_hessian(X, aff, kind, lam)
        floor = self.floor_scale * jnp.maximum(jnp.max(jnp.abs(d)), 1e-30)
        d = jnp.maximum(d, floor)
        return -G / d, state


@dataclasses.dataclass(frozen=True)
class SD:
    """The spectral direction (the paper's headline strategy).

    B = 4 * (D+ - W+_kappa) + mu I is constant; its Cholesky factor is
    computed once in `init` and every iteration costs two triangular
    backsolves — O(N^2 d), same order as the gradient itself.

    fp32 adaptations (DESIGN.md §7; the paper ran double precision):
      * mu = mu_scale * mean(diag B) (relative jitter; `mu_scale=None`
        reproduces the paper's 1e-10 * min(L+_nn)),
      * `refine` steps of iterative refinement on the triangular solve,
      * the *line search* (not the direction) caps the initial trial
        displacement — see LSConfig.max_rel_move — which tames the 1/mu
        amplification of inter-component modes when the affinity graph is
        disconnected (B is still pd, so Thm 2.1 convergence is unaffected).
    """

    name: str = "SD"
    kappa: int = -1   # -1 => no sparsification (kappa = N in paper notation)
    mu_scale: float | None = 1e-5
    refine: int = 1

    def init(self, X0, aff: Affinities, kind: str, lam) -> State:
        Wp = attractive_weights(aff, kind)
        n = Wp.shape[0]
        kappa = self.kappa if self.kappa >= 0 else n
        B = 4.0 * sparsified_attractive_matrix(Wp, kappa)
        bd = jnp.diag(B)
        if self.mu_scale is None:
            mu = 1e-10 * jnp.min(bd)          # paper's setting
        else:
            mu = jnp.maximum(1e-10 * jnp.min(bd), self.mu_scale * jnp.mean(bd))
        B = B + mu * jnp.eye(n, dtype=B.dtype)
        R = jnp.linalg.cholesky(B)  # lower
        return {"chol": R, "B": B}

    def direction(self, state, X, G, aff, kind, lam):
        R = state["chol"]
        P = -jsl.cho_solve((R, True), G)
        for _ in range(self.refine):
            resid = -G - state["B"] @ P
            P = P + jsl.cho_solve((R, True), resid)
        return P, state


@dataclasses.dataclass(frozen=True)
class SDMinus:
    """SD-: adds the psd same-dimension repulsive curvature blocks.

    B_i = 4 L+ + 8 relu(w^xx_ii)-Laplacian, one N x N block per embedding
    dimension; solved inexactly by warm-started linear CG (paper: rel tol
    0.1, <= 50 iterations).
    """

    name: str = "SD-"
    kappa: int = -1
    cg_tol: float = 0.1
    cg_maxiter: int = 50

    def init(self, X0, aff: Affinities, kind: str, lam) -> State:
        Wp = attractive_weights(aff, kind)
        n = Wp.shape[0]
        kappa = self.kappa if self.kappa >= 0 else n
        Bplus = 4.0 * sparsified_attractive_matrix(Wp, kappa)
        bd = jnp.diag(Bplus)
        mu = _jitter(jnp.min(bd), jnp.mean(bd))
        Bplus = Bplus + mu * jnp.eye(n, dtype=Bplus.dtype)
        return {"Bplus": Bplus, "prev_P": jnp.zeros_like(X0)}

    def direction(self, state, X, G, aff, kind, lam):
        n, d = X.shape
        wxx = jnp.maximum(xx_weights_ii(X, aff, kind, lam), 0.0)  # (d,N,N)
        Lxx = (
            jnp.eye(n, dtype=X.dtype)[None] * jnp.sum(wxx, axis=-1)[:, :, None]
            - wxx
        )
        B = state["Bplus"][None] + 8.0 * Lxx                       # (d,N,N)
        res = batched_cg(
            B, -G.T, state["prev_P"].T,
            tol=self.cg_tol, maxiter=self.cg_maxiter,
        )
        P = res.x.T
        return P, {**state, "prev_P": P}


@dataclasses.dataclass(frozen=True)
class SparseSD:
    """Spectral direction from ELL storage: no (N, N) array, no Cholesky.

    B = 4 (D+ - W+_k) + mu I applied matrix-free over the neighbor graph
    (sparse/linalg.py), solved by Jacobi-preconditioned CG warm-started
    from the previous direction.  Accepts either a `sparse.SparseAffinities`
    (the native large-N path: the graph IS the attractive graph, D+ its
    degree) or a dense `Affinities` (converted by per-row top-k; D+ stays
    the FULL degree, preserving the paper's kappa semantics where k = 0
    degenerates to FP and k = N-1 recovers the exact spectral direction).

    Each iteration costs O(cg_iters * N * k * d) — the same order as the
    sparse gradient itself — versus SD's O(N^2 d) backsolves.
    """

    name: str = "SparseSD"
    k: int = -1                  # ELL width for dense conversion; -1 => N-1
    mu_scale: float | None = 1e-5
    cg_tol: float = 1e-3
    cg_maxiter: int = 100

    def init(self, X0, aff, kind: str, lam) -> State:
        from repro.kernels.ops import ell_live_lengths
        from repro.sparse.graph import NeighborGraph, from_dense, reverse_graph
        from repro.sparse.linalg import sym_degree

        if hasattr(aff, "graph"):                 # SparseAffinities
            g = aff.graph
            rev = aff.rev if getattr(aff, "rev", None) is not None \
                else reverse_graph(g)
            dfull = sym_degree(g)
        else:
            Wp = attractive_weights(aff, kind)
            n = Wp.shape[0]
            if self.k == 0:
                # FP limit: an all-padding graph (L = 0), so B = 4 D+ + mu I
                g = NeighborGraph(
                    indices=jnp.arange(n, dtype=jnp.int32)[:, None],
                    weights=jnp.zeros((n, 1), Wp.dtype))
            else:
                g = from_dense(Wp, self.k if self.k > 0 else n - 1)
            rev = reverse_graph(g)
            dfull = degree(Wp)                    # paper's kappa semantics
        dsym = sym_degree(g)
        bd = 4.0 * dfull
        if self.mu_scale is None:
            mu = 1e-10 * jnp.min(bd)              # paper's setting
        else:
            mu = jnp.maximum(1e-10 * jnp.min(bd),
                             self.mu_scale * jnp.mean(bd))
        # B v = 4 L(W+_k) v + resid v + mu v; resid >= 0 keeps B pd when
        # the sparsified graph drops degree mass (cf. laplacian.py).
        resid = 4.0 * jnp.maximum(dfull - dsym, 0.0)
        return {
            "indices": g.indices, "weights": g.weights,
            "rev_indices": rev.indices, "rev_weights": rev.weights,
            # the ELL kernels' per-row trip counts, once per system
            "lengths": (ell_live_lengths(g.weights),
                        ell_live_lengths(rev.weights)),
            "shift": resid + mu, "inv_diag": 1.0 / (4.0 * dsym + resid + mu),
            "prev_P": jnp.zeros_like(X0),
        }

    def direction(self, state, X, G, aff, kind, lam):
        from repro.sparse.graph import NeighborGraph
        from repro.sparse.linalg import pcg, sym_lap_matvec

        g = NeighborGraph(state["indices"], state["weights"])
        rev = NeighborGraph(state["rev_indices"], state["rev_weights"])
        shift = state["shift"]

        def matvec(V):
            return (4.0 * sym_lap_matvec(g, V, rev=rev,
                                         lengths=state["lengths"])
                    + shift[:, None] * V)

        res = pcg(matvec, -G, state["prev_P"], inv_diag=state["inv_diag"],
                  tol=self.cg_tol, maxiter=self.cg_maxiter)
        return res.x, {**state, "prev_P": res.x}


STRATEGIES = {
    "gd": GD,
    "fp": FP,
    "diagh": DiagH,
    "sd": SD,
    "sd-": SDMinus,
    "sparsesd": SparseSD,
}


def make_strategy(name: str, **kwargs):
    try:
        return STRATEGIES[name.lower()](**kwargs)
    except KeyError:  # pragma: no cover
        raise ValueError(f"unknown strategy {name!r}; have {sorted(STRATEGIES)}")
