"""Input rows for the benchmark's configurations, made from `--seed`.

The generators are copies of `repro.data.coil_like` / `mnist_like` at the
time the benchmark was written, so that a later change to the program's
own data module cannot move the yardstick.  Each configuration draws its
rows ONCE from a fixed base seed (the configuration's `data.base_seed`):
`generate`.  A driver whose program closes over the rows (the sparse
objective bakes its graph into its compiled programs) takes them as
generated, so the persistent compile cache hits in every run, and lets
`--seed` vary only what the program takes as arguments (the initial
embedding, the negative draws, the queries).  `rows` permutes them, for
a driver that runs fixed orderings of the same rows: every ordering does
the same work in another order.
"""
from __future__ import annotations

import numpy as np


def coil_like(n_per: int, loops: int, dim: int, seed: int,
              noise: float = 0.02, separation: float = 1.2) -> np.ndarray:
    """`loops` closed 1-D manifolds in R^dim: the structure of COIL-20's
    rotation sequences (72 poses of each of 20 objects)."""
    rng = np.random.default_rng(seed)
    ts = np.linspace(0, 2 * np.pi, n_per, endpoint=False)
    pts = []
    for _ in range(loops):
        center = rng.normal(size=dim) * separation
        basis = rng.normal(size=(2, dim))
        circ = np.stack([np.cos(ts), np.sin(ts)], -1) @ basis
        pts.append(circ + center + noise * rng.normal(size=(n_per, dim)))
    return np.concatenate(pts).astype(np.float32)


def mnist_like(n: int, dim: int, seed: int, n_classes: int = 10
               ) -> tuple[np.ndarray, np.ndarray]:
    """`n_classes` anisotropic Gaussian clusters on 8-dimensional
    manifolds in R^dim: MNIST's geometry at its published shape."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, size=n)
    centers = rng.normal(size=(n_classes, dim)) * 3.0
    sub = rng.normal(size=(n_classes, 8, dim))
    z = rng.normal(size=(n, 8))
    Y = centers[labels] + np.einsum("nk,nkd->nd", z, sub[labels]) * 0.5
    Y += 0.1 * rng.normal(size=(n, dim))
    return Y.astype(np.float32), labels


def rows(data: dict, seed) -> tuple[np.ndarray, np.ndarray]:
    """(Y, labels) of a configuration's `data` block, permuted by `seed`
    (an int or a sequence of ints, as numpy's generators take it)."""
    Y, labels = generate(data)
    perm = permutation(Y.shape[0], seed)
    return Y[perm], labels[perm]


def generate(data: dict) -> tuple[np.ndarray, np.ndarray]:
    """(Y, labels) of a configuration's `data` block, in generated order."""
    gen, base = data["generator"], int(data["base_seed"])
    if gen == "coil_like":
        Y = coil_like(n_per=data["n_per"], loops=data["loops"],
                      dim=data["dim"], seed=base)
        labels = np.repeat(np.arange(data["loops"]), data["n_per"])
    elif gen == "mnist_like":
        Y, labels = mnist_like(n=data["n"], dim=data["dim"], seed=base)
    else:
        raise ValueError(f"unknown data generator {gen!r}")
    return Y, labels


def permutation(n: int, seed) -> np.ndarray:
    """The row order `rows` applies for `seed`."""
    return np.random.default_rng(seed).permutation(n)
