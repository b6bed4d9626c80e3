"""Work of one fused pairwise pass over n points in d dimensions: for every
ordered pair the squared distance, the kernel value, the attractive and
repulsive weights, the two energy sums and the two Laplacian products.

Operations per pair: 3d for the distance, 2 for the kernel, 4 for the
energy sums and 4d for the two Laplacian products (weight times the
coordinate difference, accumulated), counting a transcendental as one.
Bytes: the two (n, n) float32 weight matrices read once, X read once,
the two (n, d) products written once."""
from __future__ import annotations


def work(n: int, d: int, weight_bytes: int = 4) -> tuple[float, float]:
    pairs = float(n) * n
    flops = pairs * (7.0 * d + 6.0)
    nbytes = 2.0 * pairs * weight_bytes + 3.0 * n * d * 4
    return flops, nbytes
