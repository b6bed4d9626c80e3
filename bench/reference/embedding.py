"""Plain float64 reference of the embedding objectives the benchmark runs.

Written from the paper (Vladymyrov & Carreira-Perpinan, ICML 2012,
arXiv:1206.4646, sections 2-3) and from t-SNE's sampled-negative
estimator as the configurations state it.  It imports nothing of the
program under test: numpy and scipy on the host, and `jax.random` on the
CPU only to draw the same negative shifts from the same keys (the draw is
part of the configuration's stated estimator, a pure function of the
seed and the iteration).

Conventions (all sums over ordered pairs n != m, t = |x_n - x_m|^2):

- affinities: per-row Gaussian conditionals p_{m|n} with the bandwidth
  found by bisection so that the row's entropy is log(perplexity);
  EE uses W+ = (P + P^T) / 2 and W- = 1 off the diagonal; t-SNE stores the
  directed conditionals over the k nearest neighbours, scaled by 1/N, and
  W+ = (A + A^T) / 2.
- EE:    E = sum W+ t + lam sum W- exp(-t),
         G = 4 (L(W+) - lam L(W- exp(-t))) X.
- t-SNE (sampled): E = sum A log(1 + t) + lam log(s_hat), s_hat the
  cyclic-shift estimate of Z = sum K, K = 1 / (1 + t);
         G = 4 (L(W+ K) X - lam / z L_hat(K^2) X), z a streaming mean of
  s_hat.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


# -- affinities -------------------------------------------------------------------


def sq_distances(Y: np.ndarray) -> np.ndarray:
    Y = np.asarray(Y, np.float64)
    r = np.einsum("nd,nd->n", Y, Y)
    D2 = np.maximum(r[:, None] + r[None, :] - 2.0 * (Y @ Y.T), 0.0)
    np.fill_diagonal(D2, 0.0)
    return D2


def calibrate(d2: np.ndarray, perplexity: float, valid: np.ndarray,
              n_iter: int = 200) -> np.ndarray:
    """Row-stochastic conditionals over the `valid` entries of each row of
    `d2`, each row's entropy equal to log(perplexity) (bisection on the
    precision beta, float64)."""
    d2 = np.asarray(d2, np.float64)
    target = np.log(perplexity)
    n = d2.shape[0]
    lo = np.zeros(n)
    hi = np.full(n, np.inf)
    beta = np.ones(n)

    def probs(beta):
        logits = np.where(valid, -beta[:, None] * d2, -np.inf)
        logits -= np.max(logits, axis=1, keepdims=True)
        e = np.where(valid, np.exp(logits), 0.0)
        return e / np.sum(e, axis=1, keepdims=True)

    for _ in range(n_iter):
        p = probs(beta)
        h = -np.sum(np.where(p > 0, p * np.log(np.maximum(p, 1e-300)), 0.0),
                    axis=1)
        too_high = h > target
        lo = np.where(too_high, beta, lo)
        hi = np.where(too_high, hi, beta)
        beta = np.where(np.isinf(hi), beta * 2.0, 0.5 * (lo + hi))
        if np.all(np.isfinite(hi) & (hi - lo <= 1e-15 * hi)):
            break
    return probs(beta)


def dense_affinities(Y: np.ndarray, perplexity: float
                     ) -> tuple[np.ndarray, np.ndarray]:
    """(W+, W-) of the EE family: symmetrised conditionals, ones off the
    diagonal."""
    D2 = sq_distances(Y)
    n = D2.shape[0]
    P = calibrate(D2, perplexity, ~np.eye(n, dtype=bool))
    Wp = 0.5 * (P + P.T)
    Wm = 1.0 - np.eye(n)
    return Wp, Wm


def knn(Y: np.ndarray, k: int, block: int = 1000
        ) -> tuple[np.ndarray, np.ndarray]:
    """Exact k nearest neighbours of every row (self excluded), float64:
    (squared distances, indices), each (n, k), nearest first."""
    Y = np.asarray(Y, np.float64)
    n = Y.shape[0]
    r = np.einsum("nd,nd->n", Y, Y)
    d2_out = np.empty((n, k))
    idx_out = np.empty((n, k), np.int64)
    for a in range(0, n, block):
        b = min(n, a + block)
        d2 = np.maximum(r[a:b, None] + r[None, :] - 2.0 * (Y[a:b] @ Y.T), 0.0)
        d2[np.arange(b - a), np.arange(a, b)] = np.inf
        part = np.argpartition(d2, k, axis=1)[:, :k]
        pd = np.take_along_axis(d2, part, axis=1)
        order = np.argsort(pd, axis=1)
        idx_out[a:b] = np.take_along_axis(part, order, axis=1)
        d2_out[a:b] = np.take_along_axis(pd, order, axis=1)
    return d2_out, idx_out


def knn_conditionals(Y: np.ndarray, k: int, perplexity: float
                     ) -> tuple[np.ndarray, np.ndarray]:
    """(indices, P_cond): each row's conditionals over its k nearest
    neighbours (rows sum to 1)."""
    d2, idx = knn(Y, k)
    return idx, calibrate(d2, perplexity, np.ones_like(d2, dtype=bool))


def row_l1_gap(idx_a: np.ndarray, w_a: np.ndarray, idx_b: np.ndarray,
               w_b: np.ndarray) -> np.ndarray:
    """Per row, the L1 distance between two sparse rows given as
    (column, weight) lists; a column on one side only counts in full."""
    n = idx_a.shape[0]
    cols = np.concatenate([idx_a, idx_b], axis=1)
    vals = np.concatenate([w_a, -w_b], axis=1)
    order = np.argsort(cols, axis=1, kind="stable")
    cols = np.take_along_axis(cols, order, axis=1)
    vals = np.take_along_axis(vals, order, axis=1)
    gap = np.zeros(n)
    start = np.ones_like(cols, dtype=bool)
    start[:, 1:] = cols[:, 1:] != cols[:, :-1]
    for i in range(n):
        seg = np.add.reduceat(vals[i], np.flatnonzero(start[i]))
        gap[i] = np.sum(np.abs(seg))
    return gap


# -- EE (dense) ---------------------------------------------------------------------


def _lap(W: np.ndarray, X: np.ndarray) -> np.ndarray:
    return np.sum(W, axis=1, keepdims=True) * X - W @ X


def ee_energy_grad(X: np.ndarray, Wp: np.ndarray, Wm: np.ndarray, lam: float
                   ) -> tuple[float, np.ndarray]:
    X = np.asarray(X, np.float64)
    T = sq_distances(X)
    B = Wm * np.exp(-T)
    E = float(np.sum(Wp * T) + lam * np.sum(B))
    G = 4.0 * (_lap(Wp, X) - lam * _lap(B, X))
    return E, G


# -- t-SNE (sparse graph, sampled negatives) ----------------------------------------


class SparseTSNE:
    """The sampled t-SNE objective over a k-NN graph and its spectral
    direction, as the engine's stochastic path applies them."""

    def __init__(self, idx: np.ndarray, p_cond: np.ndarray, lam: float,
                 n_negatives: int, z_decay: float, mu_scale: float):
        n, k = idx.shape
        self.n, self.lam = n, lam
        self.m, self.z_decay = n_negatives, z_decay
        rows = np.repeat(np.arange(n), k)
        self.A = sp.csr_matrix((p_cond.reshape(-1) / n,
                                (rows, idx.reshape(-1))), shape=(n, n))
        self.W = (0.5 * (self.A + self.A.T)).tocsr()
        self.W.sum_duplicates()
        deg = np.asarray(self.W.sum(axis=1)).ravel()
        bd = 4.0 * deg
        self.mu = max(1e-10 * bd.min(), mu_scale * bd.mean())
        self.inv_diag = 1.0 / (bd + self.mu)
        self.deg = deg
        self.z = 0.0
        coo = self.W.tocoo()
        self._wr, self._wc, self._wv = coo.row, coo.col, coo.data
        acoo = self.A.tocoo()
        self._ar, self._ac, self._av = acoo.row, acoo.col, acoo.data

    def shifts(self, key) -> np.ndarray:
        import jax

        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            s = jax.random.choice(key, self.n - 1, shape=(self.m,),
                                  replace=False)
        return 1 + np.asarray(s, np.int64)

    def _negatives(self, X, shifts):
        n = self.n
        rows = np.arange(n)[:, None]
        J = (rows + shifts[None, :]) % n
        t = np.sum((X[:, None, :] - X[J]) ** 2, axis=-1)
        K = 1.0 / (1.0 + t)
        scale = (n - 1) / self.m
        return J, K, scale * np.sum(K), scale

    def energy(self, X, shifts) -> float:
        X = np.asarray(X, np.float64)
        t = np.sum((X[self._ar] - X[self._ac]) ** 2, axis=-1)
        e_plus = np.sum(self._av * np.log1p(t))
        _, _, s_hat, _ = self._negatives(X, shifts)
        return float(e_plus + self.lam * np.log(s_hat))

    def energy_grad(self, X, shifts) -> tuple[float, np.ndarray]:
        """Energy and gradient; advances the streaming partition estimate
        z exactly once, as one gradient evaluation does."""
        X = np.asarray(X, np.float64)
        n = self.n
        t = np.sum((X[self._ar] - X[self._ac]) ** 2, axis=-1)
        e_plus = np.sum(self._av * np.log1p(t))
        tw = np.sum((X[self._wr] - X[self._wc]) ** 2, axis=-1)
        Wk = sp.csr_matrix((self._wv / (1.0 + tw), (self._wr, self._wc)),
                           shape=(n, n))
        la_x = np.asarray(Wk.sum(axis=1)) * X - Wk @ X
        J, K, s_hat, scale = self._negatives(X, shifts)
        b = K * K
        cols = np.arange(shifts.shape[0])[None, :]
        Jr = (np.arange(n)[:, None] - shifts[None, :]) % n
        b_rev = b[Jr, cols]
        lb_x = 0.5 * scale * (
            np.sum(b, axis=1, keepdims=True) * X
            - np.einsum("nm,nmd->nd", b, X[J])
            + np.sum(b_rev, axis=1, keepdims=True) * X
            - np.einsum("nm,nmd->nd", b_rev, X[Jr]))
        self.z = (s_hat if self.z <= 0 else
                  self.z_decay * self.z + (1.0 - self.z_decay) * s_hat)
        E = float(e_plus + self.lam * np.log(s_hat))
        G = 4.0 * (la_x - (self.lam / self.z) * lb_x)
        return E, G

    def matvec(self, V: np.ndarray) -> np.ndarray:
        return 4.0 * (self.deg[:, None] * V - self.W @ V) + self.mu * V

    def pcg(self, Bm: np.ndarray, x0: np.ndarray, tol: float, maxiter: int
            ) -> tuple[np.ndarray, int]:
        """Jacobi-preconditioned CG on B x = Bm, all columns together."""
        b_norm = max(np.linalg.norm(Bm), 1e-30)
        x = x0.copy()
        r = Bm - self.matvec(x)
        z = self.inv_diag[:, None] * r
        p = z.copy()
        rz = np.vdot(r, z)
        k = 0
        while np.linalg.norm(r) > tol * b_norm and k < maxiter:
            Ap = self.matvec(p)
            alpha = rz / max(np.vdot(p, Ap), 1e-30)
            x = x + alpha * p
            r = r - alpha * Ap
            z = self.inv_diag[:, None] * r
            rz_new = np.vdot(r, z)
            p = z + (rz_new / max(rz, 1e-30)) * p
            rz = rz_new
            k += 1
        return x, k


# -- the line search of the SD family -------------------------------------------------


def initial_step(X, P, alpha_prev, rho, max_rel_move):
    """Adaptive-grow first trial step with the trust cap on the move."""
    alpha0 = min(alpha_prev / rho, 1.0)
    if max_rel_move is not None:
        xc = X - X.mean(axis=0, keepdims=True)
        scale = np.sqrt(np.mean(xc * xc)) + 1e-3
        p_rms = np.sqrt(np.mean(P * P)) + 1e-30
        alpha0 = min(alpha0, max_rel_move * scale / p_rms)
    return alpha0


def backtrack(energy_of, X, e0, G, P, alpha0, c1, rho, max_backtracks):
    """Armijo backtracking; returns (alpha, E(X + alpha P))."""
    gtp = float(np.vdot(G, P))
    alpha = alpha0
    for _ in range(max_backtracks):
        e_new = energy_of(X + alpha * P)
        if e_new <= e0 + c1 * alpha * gtp:
            return alpha, e_new
        alpha *= rho
    return alpha, energy_of(X + alpha * P)


def step_key(seed: int, it: int):
    """The key of iteration `it`'s negative draws (0: the pre-loop
    gradient)."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        return jax.random.fold_in(jax.random.PRNGKey(seed + 1), it)


def tsne_sd_steps(obj: SparseTSNE, X0: np.ndarray, seed: int, n_steps: int,
                  ls: dict, cg_tol: float, cg_maxiter: int):
    """The first `n_steps` iterations of the stochastic SD fit from X0:
    per step the energy at the accepted point and the iterate."""
    keys = [step_key(seed, it) for it in range(n_steps + 1)]
    X = np.asarray(X0, np.float64)
    obj.z = 0.0
    obj.energy_grad(X, obj.shifts(keys[0]))       # the engine's first call
    P_prev = np.zeros_like(X)
    alpha = 1.0
    energies, iterates = [], []
    for it in range(1, n_steps + 1):
        s = obj.shifts(keys[it])
        E, G = obj.energy_grad(X, s)
        P, _ = obj.pcg(-G, P_prev, cg_tol, cg_maxiter)
        P_prev = P
        a0 = initial_step(X, P, alpha, ls["rho"], ls["max_rel_move"])
        alpha, e_new = backtrack(lambda Xn: obj.energy(Xn, s), X, E, G, P,
                                 a0, ls["c1"], ls["rho"],
                                 ls["max_backtracks"])
        X = X + alpha * P
        energies.append(e_new)
        iterates.append(X.copy())
    return energies, iterates
