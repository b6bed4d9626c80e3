"""Work of one directed ELL Laplacian product (L(A) X)_n = deg_n x_n -
sum_j w_nj x_{i_nj} over an (n, k) graph and an (n, d) float32 X.

Operations: a multiply-add per slot and column, and the degree term per
row and column.  Bytes: what has to cross HBM whatever implements the
product -- every slot's index and weight (4 + 4 bytes), X read once and
the result written once.  The gathered neighbour rows are not counted:
X is small enough (n * d * 4 bytes) to sit in on-chip memory, so reading
it once is the least the product needs, and counting n * k row reads
could put a faster kernel above its own roofline."""
from __future__ import annotations


def work(n: int, k: int, d: int) -> tuple[float, float]:
    flops = 2.0 * n * k * d + 2.0 * n * d
    nbytes = n * k * (4 + 4) + 2.0 * n * d * 4
    return flops, nbytes
