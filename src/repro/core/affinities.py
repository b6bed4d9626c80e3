"""Input affinities: perplexity-calibrated Gaussian neighbourhoods (SNE-style).

Given high-dimensional data Y (N, D) (or a precomputed squared-distance
matrix), compute per-point conditional distributions

    p_{m|n} = exp(-beta_n ||y_n - y_m||^2) / sum_{m' != n} exp(-beta_n ...)

with beta_n found by bisection so that the entropy of P_n equals
log(perplexity).  The symmetric joint is p_nm = (p_{m|n} + p_{n|m}) / (2N)
(sums to 1 over all pairs) — exactly the W+ of s-SNE / t-SNE and a valid W+
for EE.

Everything is jit-compatible: the bisection is a fixed-iteration
jax.lax.fori_loop vmapped over rows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray


class Affinities(NamedTuple):
    """Input-side weights for the generic objective.

    Wp: attractive weights (P for normalized models, W+ for EE).
    Wm: repulsive weights (W- for EE-family; all-ones off-diagonal for
        normalized models where E- has no data weights).
    """

    Wp: Array
    Wm: Array


def sq_distances(Y: Array) -> Array:
    """Pairwise squared Euclidean distances, exact zero diagonal."""
    r = jnp.sum(Y * Y, axis=-1)
    D2 = r[:, None] + r[None, :] - 2.0 * (Y @ Y.T)
    D2 = jnp.maximum(D2, 0.0)
    n = Y.shape[0]
    return D2 * (1.0 - jnp.eye(n, dtype=D2.dtype))


def _row_entropy_probs(d2_row: Array, beta: Array, self_idx: Array) -> tuple[Array, Array]:
    """Shannon entropy (nats) and probs of one conditional distribution."""
    logits = -beta * d2_row
    logits = jnp.where(self_idx, -jnp.inf, logits)
    logits = logits - jnp.max(jnp.where(self_idx, -jnp.inf, logits))
    e = jnp.where(self_idx, 0.0, jnp.exp(logits))
    s = jnp.sum(e)
    p = e / s
    # H = -sum p log p, with 0 log 0 = 0
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-37)), 0.0))
    return h, p


@functools.partial(jax.jit, static_argnames=("n_iter",))
def calibrated_conditionals(
    D2: Array, perplexity: float, n_iter: int = 60
) -> Array:
    """Per-row bisection on beta so H(P_n) = log(perplexity).  Returns P (N,N)
    row-stochastic with zero diagonal.

    Module-level jit with `perplexity` as an operand: the eager
    vmap-of-fori_loop form rebuilt its `solve_row` closure per call, so
    every fit recompiled the bisection program (caught by the
    compile-count guard in tests/test_analysis.py); jitted here it
    compiles once per (shape, dtype) and perplexity changes are free."""
    with jax.named_scope("affinities"):
        n = D2.shape[0]
        target = jnp.log(jnp.asarray(perplexity, dtype=D2.dtype))
        eye = jnp.eye(n, dtype=bool)

        def solve_row(d2_row, self_row):
            def body(_, carry):
                lo, hi, beta = carry
                h, _ = _row_entropy_probs(d2_row, beta, self_row)
                # entropy decreases in beta: too much entropy -> raise beta
                too_high = h > target
                lo = jnp.where(too_high, beta, lo)
                hi = jnp.where(too_high, hi, beta)
                beta = jnp.where(
                    jnp.isinf(hi), beta * 2.0, 0.5 * (lo + hi)
                )
                return lo, hi, beta

            lo0 = jnp.asarray(0.0, D2.dtype)
            hi0 = jnp.asarray(jnp.inf, D2.dtype)
            beta0 = jnp.asarray(1.0, D2.dtype)
            _, _, beta = jax.lax.fori_loop(0, n_iter, body, (lo0, hi0, beta0))
            _, p = _row_entropy_probs(d2_row, beta, self_row)
            return p

        return jax.vmap(solve_row)(D2, eye)


def sne_affinities(Y: Array, perplexity: float = 30.0) -> Array:
    """Symmetric joint P (sums to 1, zero diagonal) from data Y."""
    D2 = sq_distances(Y)
    return sne_affinities_from_d2(D2, perplexity)


def sne_affinities_from_d2(D2: Array, perplexity: float = 30.0) -> Array:
    P_cond = calibrated_conditionals(D2, perplexity)
    n = D2.shape[0]
    P = (P_cond + P_cond.T) / (2.0 * n)
    return P


def make_affinities(
    Y: Array,
    perplexity: float = 30.0,
    model: str = "ee",
) -> Affinities:
    """Build (Wp, Wm) for a given model family.

    Normalized models (s-SNE / t-SNE): Wp = joint P = (P_cond + P_cond^T)/2N
    (sums to 1 over all pairs — definitional), Wm = 1 off-diagonal.

    EE-family (ee / tee / epan): Wp = symmetrized conditionals
    (P_cond + P_cond^T)/2 *without* the 1/N joint normalization — "SNE
    affinities" in the EE sense (Carreira-Perpinan 2010): row degrees ~ 1, so
    the attractive Laplacian L+ is O(1)-scaled against the lambda-weighted
    repulsion (and the SD linear system is naturally scaled).  Wm = 1
    off-diagonal as in the paper's experiments.
    """
    n = Y.shape[0]
    D2 = sq_distances(Y)
    P_cond = calibrated_conditionals(D2, perplexity)
    if model in ("ssne", "tsne"):
        Wp = (P_cond + P_cond.T) / (2.0 * n)
    else:
        Wp = 0.5 * (P_cond + P_cond.T)
    ones = 1.0 - jnp.eye(n, dtype=Wp.dtype)
    return Affinities(Wp=Wp, Wm=ones)
