"""Work of the parts of a dense EE fit that are not pairwise passes, for n
points in D input dimensions embedded in d: the squared-distance Gram
matrix (2 n^2 D), the entropy calibration (one pass over the n x n
distances), the spectral initialisation (tridiagonalising an n x n
matrix, 4/3 n^3, the least an eigensolver does), the Cholesky factor of
the spectral direction's matrix (n^3 / 3), and per iteration its two
triangular solves (2 n^2 d each, reading the factor)."""
from __future__ import annotations


def work(n: int, D: int, d: int, iters: int) -> tuple[float, float]:
    flops = (2.0 * n * n * D + 4.0 / 3.0 * n ** 3 + n ** 3 / 3.0
             + iters * 4.0 * n * n * d)
    nbytes = (n * D * 4.0 + 3.0 * n * n * 4.0
              + iters * 2.0 * n * n * 4.0)
    return flops, nbytes
