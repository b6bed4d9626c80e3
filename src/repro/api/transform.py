"""Out-of-sample `transform()`: embed unseen points against a FROZEN
training embedding, never re-fitting.

The standard fixed-anchor extension (surveyed in Ghojogh & Ghodsi 2020;
the serving motivation of FUnc-SNE): the training pairs (Y_train,
X_train) define the map, and a new point y is embedded by minimizing the
SAME attraction-repulsion energy restricted to its own coordinates, with
every training coordinate held constant:

  * attraction — kNN affinities of y against the TRAINING set, calibrated
    per row to the spec's perplexity exactly as in training
    (`sparse.graph.calibrated_weights_ell` over the `knn_cross`
    candidates; `TransformSpec.knn_method='approx'` swaps the exact
    blocked scan for the random-projection candidate search so queries
    stay cheap when the training set is large);
  * repulsion — y against `n_negatives` uniformly sampled training
    anchors, scaled by N/m (the unbiased estimate of repulsion against the
    whole training set; `exhaustive=True` runs deterministically over
    every anchor).  Normalized kinds (ssne/tsne) use each new point's OWN
    partition function over the anchors, log-weighted as in training.

Because the anchors never move, the free problem is separable across new
points (no new-new interactions), the Hessian's attractive part is
diagonal, and each `transform` costs O(n_new * (k + m) * d) per iteration
— serving-scale, independent of how long training took.

Two solvers realize the same anchored objective (`TransformSpec.solver`):

  * ``'engine'`` (default) — the PR-4 path: autodiff energy through the
    shared `fit_loop`, one global backtracking line search over the whole
    query batch.  Bit-compatible with every pinned transform trajectory.
  * ``'rowwise'`` — a fully jitted per-row solver: per-row Armijo
    backtracking on the row's own anchored energy, per-row adaptive-grow
    step, per-row convergence freezing.  No host round-trip per iteration
    and, because nothing couples rows (the sampled negative-anchor draw
    is a pure function of (seed, iteration)) and every query runs through
    one fixed row-chunk program shape (`TransformSpec.batch_size`, default
    `ROW_CHUNK`), results are INDEPENDENT of batch composition — the
    property `repro.serve`'s micro-batching and padding correctness rests
    on (docs/serving.md).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.objectives import (attractive_edge_terms, is_normalized,
                                   negative_pair_terms)
from repro.embed.engine import LoopConfig, fit_loop
from repro.obs import span
from repro.sparse.graph import calibrated_weights_ell, knn_cross

Array = jnp.ndarray


class TransformObjective:
    """Fixed-anchor objective over the new rows only (engine protocol).

    `stochastic` follows the negative draw: sampled anchors make the
    engine thread one fold_in key per iteration (common-random-numbers
    line search + EMA convergence), the exhaustive mode is deterministic.
    """

    def __init__(self, kind: str, lam, anchors: Array, nn_idx: Array,
                 nn_w: Array, n_negatives: int | None):
        n_train = anchors.shape[0]
        exhaustive = n_negatives is None or n_negatives >= n_train
        self.stochastic = not exhaustive
        self._anchors = anchors
        normalized = is_normalized(kind)
        lam = jnp.asarray(lam, anchors.dtype)

        if exhaustive:
            J0 = jnp.arange(n_train, dtype=jnp.int32)
            scale = 1.0
        else:
            scale = n_train / n_negatives

        def draw(key):
            if exhaustive:
                return J0
            return jax.random.choice(
                key, n_train, shape=(n_negatives,),
                replace=False).astype(jnp.int32)

        def energy(X, J):
            # attraction: calibrated kNN edges to fixed anchors
            t_att = jnp.sum((X[:, None, :] - anchors[nn_idx]) ** 2, axis=-1)
            e_plus = jnp.sum(attractive_edge_terms(kind, nn_w, t_att)[0])
            # repulsion: shared anchor draw J across rows
            t_neg = jnp.sum((X[:, None, :] - anchors[J]) ** 2, axis=-1)
            s_row = scale * jnp.sum(negative_pair_terms(kind, t_neg)[0],
                                    axis=1)                    # (n_new,)
            if normalized:
                # per-point partition function — the out-of-sample analogue
                # of the training models' global log Z
                return e_plus + lam * jnp.sum(
                    jnp.log(jnp.maximum(s_row, 1e-30)))
            return e_plus + lam * jnp.sum(s_row)

        self._draw = draw
        self._e = jax.jit(energy)
        self._vg = jax.jit(jax.value_and_grad(energy))
        # anchored attractive Hessian is diagonal: B = 4 diag(row deg) + mu
        # (frozen at X = 0 as in the SD family; calibrated rows sum to ~1)
        deg = jnp.sum(nn_w, axis=1)
        mu = jnp.maximum(1e-10 * jnp.min(4.0 * deg),
                         1e-5 * jnp.mean(4.0 * deg))
        self._inv_diag = 1.0 / (4.0 * deg + mu)

    def energy_and_grad(self, X, key):
        E, G = self._vg(X, self._draw(key))
        return E, G

    def energy(self, X, key):
        return self._e(X, self._draw(key))

    def make_direction_solver(self):
        def solve(state, X, G):
            return -self._inv_diag[:, None] * G, state

        return solve, ()


# -- the rowwise (batch-invariant) solver ---------------------------------------


@dataclasses.dataclass
class RowwiseResult:
    """Host-side summary of one rowwise transform solve (the lightweight
    analogue of the engine path's `EngineResult`)."""

    X: Array
    n_iters: int              # outer iterations actually run
    n_rows: int
    n_converged: int          # rows frozen by the per-row tol test


@functools.lru_cache(maxsize=64)
def _rowwise_fn(kind: str, m: int, exhaustive: bool, max_iters: int,
                tol: float, seed: int, c1: float, rho: float,
                max_backtracks: int, max_rel_move: float | None):
    """The jitted rowwise solve for one static knob combination.  jax's
    jit cache then specializes per array shape — which is exactly the
    per-batch-size compilation cache `repro.serve` buckets requests into
    (`EmbeddingServer.cache_info()` reports the keys)."""
    normalized = is_normalized(kind)

    def solve(anchors, nn_idx, nn_w, X0, lam):
        n_train = anchors.shape[0]
        lam_ = jnp.asarray(lam, anchors.dtype)
        scale = 1.0 if exhaustive else n_train / m
        J0 = jnp.arange(n_train, dtype=jnp.int32)

        def draw(it):
            if exhaustive:
                return J0
            key = jax.random.fold_in(jax.random.PRNGKey(seed), it)
            return jax.random.choice(
                key, n_train, shape=(m,), replace=False).astype(jnp.int32)

        def row_energy(X, J):
            t_att = jnp.sum((X[:, None, :] - anchors[nn_idx]) ** 2, axis=-1)
            e_rows = jnp.sum(attractive_edge_terms(kind, nn_w, t_att)[0],
                             axis=1)
            t_neg = jnp.sum((X[:, None, :] - anchors[J]) ** 2, axis=-1)
            s_row = scale * jnp.sum(negative_pair_terms(kind, t_neg)[0],
                                    axis=1)
            if normalized:
                return e_rows + lam_ * jnp.log(jnp.maximum(s_row, 1e-30))
            return e_rows + lam_ * s_row

        def total(X, J):
            e = row_energy(X, J)
            return jnp.sum(e), e

        vg = jax.value_and_grad(total, has_aux=True)

        # per-row diagonal preconditioner: B_r = 4 deg_r + mu_r with a
        # PER-ROW damping (a global mu would couple rows through the
        # batch, breaking batch-composition invariance)
        deg = jnp.sum(nn_w, axis=1)
        inv_diag = 1.0 / (4.0 * deg + jnp.maximum(4e-5 * deg, 1e-12))
        # trust cap scale: spread of the (fixed) anchor embedding
        a_c = anchors - jnp.mean(anchors, axis=0, keepdims=True)
        a_rms = jnp.sqrt(jnp.mean(a_c * a_c)) + 1e-3

        n_rows = X0.shape[0]

        def outer_cond(carry):
            it, X, alpha_prev, frozen = carry
            return (it < max_iters) & ~jnp.all(frozen)

        def outer_body(carry):
            it, X, alpha_prev, frozen = carry
            J = draw(it)
            (_, e_rows), G = vg(X, J)
            P = -inv_diag[:, None] * G
            dgp = jnp.sum(G * P, axis=1)
            # adaptive-grow init + per-row trust cap (engine policy,
            # vectorized over rows)
            alpha = jnp.minimum(alpha_prev / rho, 1.0)
            if max_rel_move is not None:
                p_rms = jnp.sqrt(jnp.mean(P * P, axis=1)) + 1e-30
                alpha = jnp.minimum(alpha, max_rel_move * a_rms / p_rms)

            ok0 = frozen
            alpha0 = jnp.where(frozen, 0.0, alpha)

            def bt_cond(c):
                _, ok, _, tries = c
                return ~jnp.all(ok) & (tries < max_backtracks)

            def bt_body(c):
                a, ok, e_new, tries = c
                Xt = X + a[:, None] * P
                e_t = row_energy(Xt, J)
                ok_now = e_t <= e_rows + c1 * a * dgp
                e_new = jnp.where(~ok & ok_now, e_t, e_new)
                a = jnp.where(ok | ok_now, a, a * rho)
                return a, ok | ok_now, e_new, tries + 1

            alpha_f, ok, e_new, _ = jax.lax.while_loop(
                bt_cond, bt_body, (alpha0, ok0, e_rows, 0))
            failed = ~ok & ~frozen          # line search exhausted
            alpha_f = jnp.where(ok & ~frozen, alpha_f, 0.0)
            X = X + alpha_f[:, None] * P
            # per-row raw convergence on the CRN pair (same J)
            rel = jnp.abs(e_rows - e_new) / jnp.maximum(
                jnp.abs(e_rows), 1e-30)
            frozen = frozen | failed | (~frozen & (rel < tol))
            alpha_prev = jnp.where(alpha_f > 0, alpha_f, alpha_prev)
            return it + 1, X, alpha_prev, frozen

        it0 = jnp.asarray(0, jnp.int32)
        alpha0 = jnp.ones((n_rows,), X0.dtype)
        frozen0 = jnp.zeros((n_rows,), bool)
        it, X, _, frozen = jax.lax.while_loop(
            outer_cond, outer_body, (it0, X0, alpha0, frozen0))
        return X, it, frozen

    return jax.jit(solve)


def rowwise_transform(kind: str, lam, anchors: Array, nn_idx: Array,
                      nn_w: Array, X0: Array, *,
                      n_negatives: int | None, max_iters: int, tol: float,
                      seed: int, ls, n_valid: int | None = None
                      ) -> RowwiseResult:
    """Solve the anchored problem row-independently (see module docstring).
    `n_negatives=None` (or >= n_train) is the exhaustive deterministic
    mode.  Only the first `n_valid` rows (default: all) are real; the rest
    are padding, left out of the counts.  Returns a `RowwiseResult`."""
    n_train = anchors.shape[0]
    exhaustive = n_negatives is None or n_negatives >= n_train
    fn = _rowwise_fn(kind, 0 if exhaustive else int(n_negatives),
                     exhaustive, int(max_iters), float(tol), int(seed),
                     float(ls.c1), float(ls.rho), int(ls.max_backtracks),
                     None if ls.max_rel_move is None
                     else float(ls.max_rel_move))
    n_rows = int(X0.shape[0]) if n_valid is None else int(n_valid)
    X, it, frozen = fn(anchors, nn_idx, nn_w, X0, lam)
    # one batched transfer for both counters (RPR001) — a serve-path
    # call pays a single device->host round-trip, not two
    it_h, n_conv_h = jax.device_get((it, jnp.sum(frozen[:n_rows])))
    return RowwiseResult(X=X[:n_rows], n_iters=int(it_h), n_rows=n_rows,
                         n_converged=int(n_conv_h))


# -- cross affinities -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "k", "perplexity", "method", "n_projections", "window", "knn_seed"))
def _anchor_affinities(Y_new, Y_train, k: int, perplexity: float,
                       method: str = "exact", n_projections: int = 8,
                       window: int = 16, knn_seed: int = 0):
    kw = ({"n_projections": n_projections, "window": window,
           "seed": knn_seed} if method == "approx" else {})
    d2, idx = knn_cross(Y_new, Y_train, k, method=method, **kw)
    # approx candidates can carry +inf duplicate markers; their calibrated
    # weight is exactly 0, so they behave like padded slots
    w = calibrated_weights_ell(d2, jnp.ones_like(idx, dtype=bool),
                               perplexity)
    return idx, w


#: rows per rowwise solve program when `TransformSpec.batch_size` is 0
#: (one sublane tile of f32 rows on a TPU)
ROW_CHUNK = 8


def resolve_transform_spec(spec, tspec):
    """Fill a `TransformSpec`'s deferred (zero/None) fields from the
    fitted `EmbedSpec`; returns the concrete spec serving will use."""
    from .spec import TransformSpec
    if tspec is None:
        tspec = TransformSpec()
    changes = {}
    if tspec.max_iters == 0:
        changes["max_iters"] = int(spec.transform_iters)
    if tspec.n_negatives == 0:
        changes["n_negatives"] = int(spec.transform_negatives)
    if tspec.tol is None:
        changes["tol"] = float(spec.tol)
    return tspec.replace(**changes) if changes else tspec


def _resolve_k(spec, tspec, n_train: int, perplexity: float) -> int:
    k = tspec.k_cross or spec.n_neighbors or int(3 * perplexity)
    k = min(k, n_train)
    if k < perplexity:
        raise ValueError(
            f"transform k={k} < perplexity={perplexity}: the "
            f"candidate entropy cannot reach log(perplexity) "
            f"(use more training points or a smaller perplexity)")
    return k


def transform_points(spec, Y_train: Array, X_train: Array, Y_new: Array,
                     *, tspec=None):
    """Embed `Y_new` against the frozen (Y_train, X_train) map.

    Configuration comes from a `TransformSpec` (`tspec`; None means
    `TransformSpec()`).  Returns `(X_new, result)` where `result` is an
    `EngineResult` (engine solver), a `RowwiseResult` (rowwise solver), or
    None for an empty batch.  X_train is only ever READ — the training
    embedding stays bit-identical through any number of transforms.
    """
    tspec = resolve_transform_spec(spec, tspec)

    Y_train = jnp.asarray(Y_train)
    Y_new = jnp.asarray(Y_new)
    anchors = jnp.asarray(X_train)
    if Y_new.shape[0] == 0:
        return jnp.zeros((0, anchors.shape[1]), anchors.dtype), None
    n_train = Y_train.shape[0]
    k = _resolve_k(spec, tspec, n_train, spec.perplexity)
    from repro.sparse.graph import CROSS_APPROX_N
    method = tspec.knn_method
    if method == "auto":
        method = "exact" if n_train <= CROSS_APPROX_N else "approx"
    m = None if tspec.exhaustive else tspec.n_negatives

    def affinities(Yq):
        with span("cross-knn", phase=True, n_new=int(Yq.shape[0]), k=k,
                  method=method):
            idx, w = jax.block_until_ready(_anchor_affinities(
                Yq, Y_train, k, float(spec.perplexity), method=method,
                n_projections=tspec.n_projections, window=tspec.window,
                knn_seed=tspec.seed))
        # init each new point at its calibrated anchor barycenter — already
        # a good embedding when the neighborhood is coherent; the fit
        # sharpens it
        return idx, w, jnp.einsum("mk,mkd->md", w, anchors[idx])

    if tspec.solver == "rowwise":
        # every query runs through ONE program shape: chunks of `c` rows,
        # the last padded with copies of its first row.  XLA lowers
        # reductions per shape and the per-row Armijo branch amplifies
        # last-bit differences, so a batch-sized program would make a
        # row's answer depend on the batch it arrived in
        c = tspec.batch_size or ROW_CHUNK
        n_new = int(Y_new.shape[0])
        outs, iters, conv = [], 0, 0
        for i in range(0, n_new, c):
            Yc = Y_new[i:i + c]
            r = int(Yc.shape[0])
            if r < c:
                Yc = jnp.concatenate(
                    [Yc, jnp.repeat(Yc[:1], c - r, axis=0)], axis=0)
            idx, w, X0 = affinities(Yc)
            res = rowwise_transform(
                spec.kind, spec.lam, anchors, idx, w, X0, n_negatives=m,
                max_iters=tspec.max_iters, tol=tspec.tol, seed=tspec.seed,
                ls=spec.resolved_ls(), n_valid=r)
            outs.append(res.X)
            iters = max(iters, res.n_iters)
            conv += res.n_converged
        res = RowwiseResult(X=jnp.concatenate(outs, axis=0), n_iters=iters,
                            n_rows=n_new, n_converged=conv)
        return res.X, res

    idx, w, X0 = affinities(Y_new)
    obj = TransformObjective(spec.kind, spec.lam, anchors, idx, w, m)
    cfg = LoopConfig(
        max_iters=tspec.max_iters,
        tol=tspec.tol,
        ls=spec.resolved_ls(),
        seed=tspec.seed if tspec.seed else spec.seed,
    )
    res = fit_loop(obj, X0, cfg)
    return res.X, res
