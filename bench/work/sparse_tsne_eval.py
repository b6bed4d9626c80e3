"""Work of one evaluation of the sampled t-SNE objective over an (n, k)
graph, its (n, k_rev) reverse and m cyclic-shift negatives per point, d
columns.  With `grad`, the attractive Laplacian product over both graphs
and the negatives' product over both shift directions are added.
Counted as gathers (bench/work/ell_lap_matvec.py's model): index and
weight bytes per slot where the graph stores them, X read once."""
from __future__ import annotations


def work(n: int, k: int, k_rev: int, m: int, d: int, grad: bool
         ) -> tuple[float, float]:
    slots = n * k + n * m                       # energy: edges + negatives
    flops = slots * (3.0 * d + 4.0)
    nbytes = n * k * 8.0 + n * d * 4.0
    if grad:
        lap = n * (k + k_rev) + 2 * n * m
        flops += lap * (2.0 * d + 4.0)
        nbytes += n * k_rev * 8.0 + n * d * 4.0   # the reverse graph, G
    return flops, nbytes
