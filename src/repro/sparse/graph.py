"""k-NN neighbor graphs in padded neighbor-list (ELL) format.

The paper's spectral direction is "simple, scalable" because B = 4 L+_kappa
is sparse when the attractive graph is a kappa-NN graph; this module is the
storage layer that makes that sparsity real instead of "exact zeros in a
dense (N, N) array" (core/laplacian.py).

Format — `NeighborGraph(indices (N, k) int32, weights (N, k) float)`:

  * row n lists the columns of a DIRECTED weight matrix A: A[n, indices[n,j]]
    = weights[n, j].  Duplicate columns are allowed and sum (all operators
    are linear accumulations over slots).
  * padding invariant: an unused slot stores `indices[n, j] = n` (self) with
    `weights[n, j] = 0`.  A self-edge with zero weight contributes exactly
    zero to every operator in linalg.py — twice over: Laplacian terms are
    w * (x_n - x_m) and w = 0.

Symmetric quantities (the W+ of the paper) are never materialized: operators
in linalg.py apply (A + A^T) / 2 implicitly via gather + scatter, so a
directed calibrated graph is all we ever store.  This keeps the ELL width at
k (a symmetrized union graph has unbounded in-degree and does not fit a
fixed-width row).

Construction is O(N^2 D / block) exact-blocked, or O(T N (log N + w D))
approximate via random-projection windows (`method='approx'`): T random 1-D
projections, candidates = a window of 2*w sorted neighbors per projection,
exact distances on the candidate union.  Recall is high on manifold data
because close points are close in most projections (FUnc-SNE / LargeVis use
the same trick with trees).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import span

Array = jnp.ndarray


class NeighborGraph(NamedTuple):
    """Directed ELL graph: A[n, indices[n, j]] = weights[n, j]."""

    indices: Array  # (N, k) int32
    weights: Array  # (N, k) float

    @property
    def n(self) -> int:
        return self.indices.shape[0]

    @property
    def k(self) -> int:
        return self.indices.shape[1]


class SparseAffinities(NamedTuple):
    """Sparse analogue of core.affinities.Affinities.

    graph: directed calibrated conditionals (model scaling folded into the
           weights, see `sparse_affinities`); the attractive W+ is the
           implicit (A + A^T)/2.
    rev:   the transpose A^T as a second ELL graph (`reverse_graph`), so the
           symmetric operator is two gathers — XLA's CPU scatter is ~400x
           slower than the gather at N = 10^4, and the CG solve applies the
           operator ~50x per iteration.
    Repulsive weights are implicitly W- = 1 off-diagonal (all supported
    models), estimated by negative sampling (core/objectives.py).
    """

    graph: NeighborGraph
    rev: NeighborGraph | None = None


# -- construction ---------------------------------------------------------------


def _block_topk(Y: Array, Yb: Array, row0: int, k: int) -> tuple[Array, Array]:
    """Exact k smallest squared distances from rows of Yb to all of Y."""
    r = jnp.sum(Y * Y, axis=-1)
    rb = jnp.sum(Yb * Yb, axis=-1)
    d2 = jnp.maximum(rb[:, None] + r[None, :] - 2.0 * (Yb @ Y.T), 0.0)
    rows = row0 + jnp.arange(Yb.shape[0])
    d2 = d2.at[jnp.arange(Yb.shape[0]), rows].set(jnp.inf)  # exclude self
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx.astype(jnp.int32)


def knn_graph_exact(Y: Array, k: int, block_rows: int = 1024
                    ) -> tuple[Array, Array]:
    """Exact blocked k-NN: (d2 (N, k), indices (N, k)).  O(N^2 D) compute,
    O(block_rows * N) memory."""
    n = Y.shape[0]
    if k >= n:
        raise ValueError(f"k={k} must be < N={n}")
    br = min(block_rows, n)
    n_pad = -(-n // br) * br
    Yp = jnp.pad(Y, ((0, n_pad - n), (0, 0)))

    def one_block(row0):
        Yb = jax.lax.dynamic_slice_in_dim(Yp, row0, br, axis=0)
        return _block_topk(Y, Yb, row0, k)

    d2, idx = jax.lax.map(one_block, jnp.arange(0, n_pad, br))
    return d2.reshape(n_pad, k)[:n], idx.reshape(n_pad, k)[:n]


def _dedupe_sorted_rows(idx: Array, d2: Array) -> tuple[Array, Array]:
    """Per row, mark repeated candidate columns (after sort) with +inf."""
    order = jnp.argsort(idx, axis=-1)
    idx_s = jnp.take_along_axis(idx, order, axis=-1)
    d2_s = jnp.take_along_axis(d2, order, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(idx_s[:, :1], dtype=bool),
         idx_s[:, 1:] == idx_s[:, :-1]], axis=-1)
    return idx_s, jnp.where(dup, jnp.inf, d2_s)


def knn_graph_approx(Y: Array, k: int, n_projections: int = 8,
                     window: int = 16, seed: int = 0,
                     block_rows: int = 1024) -> tuple[Array, Array]:
    """Approximate k-NN via random-projection windows.

    Candidates per point: its 2*window neighbors in sorted order along each
    of `n_projections` random directions (union, deduped), then exact
    distances and top-k on the candidate set only — O(T N w D) instead of
    O(N^2 D)."""
    n, _ = Y.shape
    if k >= n:
        raise ValueError(f"k={k} must be < N={n}")
    keys = jax.random.split(jax.random.PRNGKey(seed), n_projections)
    offs = jnp.concatenate(
        [jnp.arange(-window, 0), jnp.arange(1, window + 1)])

    def candidates_for(key):
        u = jax.random.normal(key, (Y.shape[1],), dtype=Y.dtype)
        order = jnp.argsort(Y @ u)                       # (N,) point ids
        rank = jnp.argsort(order)                        # point -> position
        pos = jnp.clip(rank[:, None] + offs[None, :], 0, n - 1)
        return order[pos]                                # (N, 2w)

    cand = jnp.concatenate([candidates_for(kk) for kk in keys], axis=-1)
    cand = cand.astype(jnp.int32)                        # (N, C)

    br = min(block_rows, n)
    n_pad = -(-n // br) * br
    Yp = jnp.pad(Y, ((0, n_pad - n), (0, 0)))
    cand_p = jnp.pad(cand, ((0, n_pad - n), (0, 0)))

    def one_block(row0):
        Yb = jax.lax.dynamic_slice_in_dim(Yp, row0, br, axis=0)
        cb = jax.lax.dynamic_slice_in_dim(cand_p, row0, br, axis=0)
        Yc = Y[cb]                                       # (br, C, D)
        d2 = jnp.maximum(
            jnp.sum(Yb * Yb, axis=-1)[:, None]
            + jnp.sum(Yc * Yc, axis=-1)
            - 2.0 * jnp.einsum("bd,bcd->bc", Yb, Yc), 0.0)
        rows = row0 + jnp.arange(br)
        d2 = jnp.where(cb == rows[:, None], jnp.inf, d2)  # exclude self
        cb_s, d2_s = _dedupe_sorted_rows(cb, d2)
        neg, slot = jax.lax.top_k(-d2_s, k)
        return -neg, jnp.take_along_axis(cb_s, slot, axis=-1)

    d2, idx = jax.lax.map(one_block, jnp.arange(0, n_pad, br))
    return d2.reshape(n_pad, k)[:n], idx.reshape(n_pad, k)[:n]


#: reference-set size above which ``knn_cross(method="auto")`` switches
#: from the exact blocked pass to the random-projection candidate search
#: (same threshold as the self-kNN `knn_graph` auto policy).
CROSS_APPROX_N = 20_000


def _validate_cross_k(k: int, n_r: int) -> None:
    """Up-front `knn_cross` argument check: a clear ValueError at the call
    boundary instead of a shape error from `top_k` deep inside the blocked
    distance loop (the serving path hits this with user-supplied
    `k_cross` against a possibly tiny training set)."""
    if k < 1:
        raise ValueError(f"knn_cross needs k >= 1, got k={k}")
    if k > n_r:
        raise ValueError(
            f"knn_cross k={k} exceeds the reference-set size "
            f"n_train={n_r}: each query needs k distinct training "
            f"neighbors (lower k_cross or provide more training points)")


def knn_cross_exact(Yq: Array, Yr: Array, k: int, block_rows: int = 1024
                    ) -> tuple[Array, Array]:
    """Exact blocked k-NN from QUERY rows to REFERENCE rows: (d2, indices),
    both (n_q, k), indices into Yr.  No self-exclusion — the two sets are
    distinct by construction (the out-of-sample transform's new points vs
    the training set).  O(n_q * n_r * D) compute, O(block_rows * n_r)
    memory, same blocking as `knn_graph_exact`."""
    n_q, n_r = Yq.shape[0], Yr.shape[0]
    _validate_cross_k(k, n_r)
    if n_q == 0:
        return (jnp.zeros((0, k), Yr.dtype),
                jnp.zeros((0, k), jnp.int32))
    r = jnp.sum(Yr * Yr, axis=-1)
    br = min(block_rows, n_q)
    n_pad = -(-n_q // br) * br
    Yp = jnp.pad(Yq, ((0, n_pad - n_q), (0, 0)))

    def one_block(row0):
        Yb = jax.lax.dynamic_slice_in_dim(Yp, row0, br, axis=0)
        d2 = jnp.maximum(
            jnp.sum(Yb * Yb, axis=-1)[:, None] + r[None, :]
            - 2.0 * (Yb @ Yr.T), 0.0)
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx.astype(jnp.int32)

    d2, idx = jax.lax.map(one_block, jnp.arange(0, n_pad, br))
    return d2.reshape(n_pad, k)[:n_q], idx.reshape(n_pad, k)[:n_q]


def knn_cross_approx(Yq: Array, Yr: Array, k: int, n_projections: int = 8,
                     window: int = 16, seed: int = 0,
                     block_rows: int = 1024) -> tuple[Array, Array]:
    """Approximate cross-set k-NN via the same random-projection windows
    as `knn_graph_approx`, extended to two point sets.

    Per projection u: the REFERENCE set is sorted along u once, each query
    is inserted by `searchsorted`, and its candidates are the 2*window
    reference points flanking the insertion slot.  The candidate union
    over `n_projections` directions gets exact distances and top-k —
    O(T n_r (log n_r + D) + T n_q w D) instead of the exact pass's
    O(n_q n_r D), so serving cost stays flat as the training set grows
    (docs/serving.md discusses the recall/latency tradeoff)."""
    n_q, n_r = Yq.shape[0], Yr.shape[0]
    _validate_cross_k(k, n_r)
    cand_per_proj = min(2 * window, n_r)
    if k > n_projections * cand_per_proj:
        raise ValueError(
            f"knn_cross approx mode: k={k} exceeds the candidate budget "
            f"{n_projections} projections x {cand_per_proj} window points"
            f" = {n_projections * cand_per_proj}; raise window or "
            f"n_projections (or use method='exact')")
    if n_q == 0:
        return (jnp.zeros((0, k), Yr.dtype),
                jnp.zeros((0, k), jnp.int32))
    keys = jax.random.split(jax.random.PRNGKey(seed), n_projections)
    offs = jnp.concatenate(
        [jnp.arange(-window, 0), jnp.arange(0, window)])

    def candidates_for(key):
        u = jax.random.normal(key, (Yr.shape[1],), dtype=Yr.dtype)
        pr = Yr @ u
        order = jnp.argsort(pr)                          # (n_r,) ref ids
        slot = jnp.searchsorted(pr[order], Yq @ u)       # (n_q,)
        pos = jnp.clip(slot[:, None] + offs[None, :], 0, n_r - 1)
        return order[pos]                                # (n_q, 2w)

    cand = jnp.concatenate([candidates_for(kk) for kk in keys], axis=-1)
    cand = cand.astype(jnp.int32)                        # (n_q, C)

    br = min(block_rows, n_q)
    n_pad = -(-n_q // br) * br
    Yp = jnp.pad(Yq, ((0, n_pad - n_q), (0, 0)))
    cand_p = jnp.pad(cand, ((0, n_pad - n_q), (0, 0)))

    def one_block(row0):
        Yb = jax.lax.dynamic_slice_in_dim(Yp, row0, br, axis=0)
        cb = jax.lax.dynamic_slice_in_dim(cand_p, row0, br, axis=0)
        Yc = Yr[cb]                                      # (br, C, D)
        d2 = jnp.maximum(
            jnp.sum(Yb * Yb, axis=-1)[:, None]
            + jnp.sum(Yc * Yc, axis=-1)
            - 2.0 * jnp.einsum("bd,bcd->bc", Yb, Yc), 0.0)
        cb_s, d2_s = _dedupe_sorted_rows(cb, d2)
        # duplicate slots score +inf; with k <= the distinct candidate
        # floor (validated above) the top-k never selects one
        neg, slot = jax.lax.top_k(-d2_s, k)
        return -neg, jnp.take_along_axis(cb_s, slot, axis=-1)

    d2, idx = jax.lax.map(one_block, jnp.arange(0, n_pad, br))
    return d2.reshape(n_pad, k)[:n_q], idx.reshape(n_pad, k)[:n_q]


def knn_cross(Yq: Array, Yr: Array, k: int, block_rows: int = 1024,
              method: str = "exact", **approx_kw) -> tuple[Array, Array]:
    """Cross-set k-NN dispatch: (d2, indices), both (n_q, k), indices into
    the reference rows `Yr`.  `method`: 'exact' (blocked O(n_q n_r D)
    pass) | 'approx' (random-projection candidate windows, `knn_cross_
    approx`) | 'auto' (exact up to n_r = CROSS_APPROX_N, approx above —
    the serving policy: queries against a large frozen training set must
    not pay a full scan).  Validates 1 <= k <= n_reference up front."""
    _validate_cross_k(k, Yr.shape[0])
    if method == "auto":
        method = "exact" if Yr.shape[0] <= CROSS_APPROX_N else "approx"
    if method == "exact":
        return knn_cross_exact(Yq, Yr, k, block_rows=block_rows)
    if method == "approx":
        return knn_cross_approx(Yq, Yr, k, block_rows=block_rows,
                                **approx_kw)
    raise ValueError(f"unknown knn_cross method {method!r}; "
                     f"have 'exact' | 'approx' | 'auto'")


def knn_graph(Y: Array, k: int, method: str = "auto", **kw) -> tuple[Array, Array]:
    """(d2, indices), both (N, k).  `method`: 'exact' | 'approx' | 'auto'
    (exact below N=20_000, approx above)."""
    if method == "auto":
        method = "exact" if Y.shape[0] <= 20_000 else "approx"
    if method == "exact":
        return knn_graph_exact(Y, k, **kw)
    if method == "approx":
        return knn_graph_approx(Y, k, **kw)
    raise ValueError(f"unknown knn method {method!r}")


# -- perplexity calibration over k candidates -----------------------------------


def _row_entropy_probs_ell(d2_row: Array, beta: Array, valid: Array
                           ) -> tuple[Array, Array]:
    logits = jnp.where(valid, -beta * d2_row, -jnp.inf)
    logits = logits - jnp.max(logits)
    e = jnp.where(valid, jnp.exp(logits), 0.0)
    p = e / jnp.sum(e)
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-37)), 0.0))
    return h, p


@functools.partial(jax.jit, static_argnames=("n_iter",))
def calibrated_weights_ell(d2: Array, valid: Array, perplexity: float,
                           n_iter: int = 60) -> Array:
    """Per-row bisection on beta over only the k candidate distances, so
    H(P_n) = log(perplexity).  Identical algorithm to
    core.affinities.calibrated_conditionals, restricted to the neighbor
    list; `valid` masks padded slots (their probability is exactly 0).

    With perplexity >= k the entropy target log(perplexity) exceeds the
    k-atom maximum log(k); bisection then drives beta -> 0 and the row
    degenerates to uniform over its candidates — callers should keep
    k >~ 3 * perplexity (t-SNE convention)."""
    with jax.named_scope("affinities"):
        target = jnp.log(jnp.asarray(perplexity, dtype=d2.dtype))

        def solve_row(d2_row, valid_row):
            def body(_, carry):
                lo, hi, beta = carry
                h, _ = _row_entropy_probs_ell(d2_row, beta, valid_row)
                too_high = h > target
                lo = jnp.where(too_high, beta, lo)
                hi = jnp.where(too_high, hi, beta)
                beta = jnp.where(jnp.isinf(hi), beta * 2.0, 0.5 * (lo + hi))
                return lo, hi, beta

            lo0 = jnp.asarray(0.0, d2.dtype)
            hi0 = jnp.asarray(jnp.inf, d2.dtype)
            beta0 = jnp.asarray(1.0, d2.dtype)
            _, _, beta = jax.lax.fori_loop(0, n_iter, body, (lo0, hi0, beta0))
            _, p = _row_entropy_probs_ell(d2_row, beta, valid_row)
            return p

        return jax.vmap(solve_row)(d2, valid)


def sparse_affinities(Y: Array, k: int, perplexity: float = 30.0,
                      model: str = "ee", method: str = "auto",
                      **knn_kw) -> SparseAffinities:
    """Sparse analogue of core.affinities.make_affinities.

    The stored directed weights A are the calibrated conditionals P_cond
    (restricted to k candidates), scaled so the implicit symmetric
    (A + A^T)/2 matches the dense convention:

      EE-family:          W+ = (P_cond + P_cond^T) / 2      -> A = P_cond
      normalized models:  W+ = (P_cond + P_cond^T) / (2N)   -> A = P_cond / N
    """
    n = Y.shape[0]
    with span("graph-build", phase=True, n=n, k=k):
        with span("graph-build/knn", method=method):
            d2, idx = jax.block_until_ready(
                knn_graph(Y, k, method=method, **knn_kw))
        valid = idx != jnp.arange(n, dtype=idx.dtype)[:, None]
        with span("graph-build/calibrate", perplexity=perplexity):
            w = jax.block_until_ready(
                calibrated_weights_ell(d2, valid, perplexity))
        if model in ("ssne", "tsne"):
            w = w / n
        # padding invariant (invalid slots: self index, zero weight)
        idx = jnp.where(valid, idx, jnp.arange(n, dtype=idx.dtype)[:, None])
        w = jnp.where(valid, w, 0.0)
        g = NeighborGraph(indices=idx, weights=w)
        with span("graph-build/reverse"):
            rev = reverse_graph(g)
    return SparseAffinities(graph=g, rev=rev)


def reverse_graph(g: NeighborGraph, width: int | None = None) -> NeighborGraph:
    """The transpose A^T as an ELL graph: row m lists every n with an edge
    n -> m, at A's weight.  Row width is the maximum in-degree (concrete,
    so this must run OUTSIDE jit — it is a build-time step, like the k-NN
    search itself); shorter rows get the standard padding (self index,
    zero weight).

    Why: the implicit symmetrization W = (A + A^T)/2 then needs only row
    GATHERS — L(W)X = (L(A)X + L(A^T)X)/2 — where the naive A^T X is a
    scatter-add, which XLA's CPU backend executes ~400x slower than the
    equivalent gather at N = 10^4.  The CG spectral solve applies the
    operator tens of times per outer iteration, so the hot loop must be
    scatter-free.  Original padded slots (zero-weight self-edges) carry
    their zero weight into the reverse rows and still contribute nothing.
    """
    n, k = g.indices.shape
    src = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[:, None], (n, k)).reshape(-1)
    dst = g.indices.reshape(-1).astype(jnp.int32)
    w = g.weights.reshape(-1)
    if width is None:
        in_deg = jnp.zeros(n, jnp.int32).at[dst].add(1)
        width = int(jnp.max(in_deg))        # concretizes: build-time only
    order = jnp.argsort(dst)
    dsts, srcs, ws = dst[order], src[order], w[order]
    # slot of each edge within its destination row
    row_start = jnp.searchsorted(dsts, jnp.arange(n, dtype=dsts.dtype))
    slot = jnp.arange(n * k) - row_start[dsts]
    rev_idx = jnp.full((n, width), -1, jnp.int32).at[dsts, slot].set(srcs)
    rev_w = jnp.zeros((n, width), g.weights.dtype).at[dsts, slot].set(ws)
    self_col = jnp.arange(n, dtype=jnp.int32)[:, None]
    return NeighborGraph(indices=jnp.where(rev_idx < 0, self_col, rev_idx),
                         weights=rev_w)


# -- dense conversions ----------------------------------------------------------


def from_dense(W: Array, k: int) -> NeighborGraph:
    """Top-k per row of a dense weight matrix as a directed ELL graph.
    The diagonal is excluded; rows with fewer than k nonzeros get padded
    slots (self index, zero weight)."""
    n = W.shape[0]
    if k >= n:
        k = n - 1
    eye = jnp.eye(n, dtype=bool)
    Wo = jnp.where(eye, -jnp.inf, W)
    vals, idx = jax.lax.top_k(Wo, k)
    keep = vals > 0
    idx = jnp.where(keep, idx, jnp.arange(n)[:, None]).astype(jnp.int32)
    return NeighborGraph(indices=idx, weights=jnp.where(keep, vals, 0.0))


def to_dense(g: NeighborGraph) -> Array:
    """Dense directed A with duplicate slots summed; padded slots (zero
    weight) contribute nothing even though they target the diagonal."""
    n, _ = g.indices.shape
    A = jnp.zeros((n, n), dtype=g.weights.dtype)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], g.indices.shape)
    return A.at[rows, g.indices].add(g.weights)
