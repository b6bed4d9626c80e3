"""Row-sharded sparse backend: the ELL neighbor graph on a device mesh.

Multi-device analogue of the single-device sparse pipeline
(sparse/linalg.py + core/objectives.energy_and_grad_sparse), built on
`shard_map` over the mesh's row axes:

  * the directed ELL graph AND its precomputed reverse (transpose) graph
    are row-sharded `P(row_axes, None)` — the reverse graph is what makes
    the implicit symmetrization W = (A + Aᵀ)/2 gather-only per shard, so
    no all-to-all and no scatter anywhere in the hot path;
  * X (N, d) is replicated — a "replicated-X epoch": each shard gathers
    arbitrary neighbor rows of X locally, and re-replicating the updated
    rows costs one O(N·d) psum per application, the same order as the
    dense path's gradient psum (NOT O(N·k));
  * only the energy/degree scalars are additionally psum'd.

Every collective reduces over the row axes only: the other mesh axes have
size 1 (`validate_sparse_mesh`) and the per-shard values do not vary along
them, and jax refuses a psum over an axis its input is invariant on.

The CG hot loop (sparse/linalg.pcg) runs unchanged on replicated (N, d)
arrays; only the operator application is shard_mapped, and it stays
scatter-free per shard.  Negative sampling keeps the cyclic-shift
structure of `energy_and_grad_sparse`: the transpose of the sampled edge
set is the negated shifts, so the reverse half of the repulsive Laplacian
is again a local gather — b_rev[n, j] is recomputed from the symmetric
distance ‖x_n − x_{(n−s_j) mod N}‖² instead of being fetched from another
shard's b.

Rows are padded to a multiple of the row-group count; padded rows carry
zero weights (exact-zero contribution, the ELL padding invariant) and are
masked out of the negative-sampling terms.

Normalized models (ssne/tsne) run through the same machinery with the
ratio-estimator repulsion (core.objectives.energy_and_grad_sparse): each
shard's partial partition-function estimate rides the SAME psum as the
attractive energy (one collective, two scalars), and the streaming-Z EMA
update is computed replicated from the psum'd total, so every shard holds
the identical z and the gradient's λ/Z factor needs no extra traffic.

The mesh may have extra (column) axes only at size 1: the ELL arrays are
one-dimensional in the row direction, so there is nothing to shard a >1
column axis over — `validate_sparse_mesh` rejects such shapes with a
clear error instead of silently running replicated.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.objectives import (attractive_edge_terms, directed_lap_apply,
                                   is_normalized, negative_pair_terms)
from repro.kernels import ops
from repro.launch.mesh import linear_row_index, shard_map, shard_map_norep
from repro.obs import span

from .graph import SparseAffinities, reverse_graph
from .linalg import make_sd_operator

Array = jnp.ndarray


class ShardedSparseGraph(NamedTuple):
    """Row-sharded, row-padded ELL graph + reverse graph on a mesh."""

    indices: Array       # (n_pad, k) int32, P(row_axes, None)
    weights: Array       # (n_pad, k)
    rev_indices: Array   # (n_pad, k_rev) int32
    rev_weights: Array   # (n_pad, k_rev)
    n: int               # true row count (n_pad - n padded zero rows)
    n_pad: int


def validate_sparse_mesh(mesh: Mesh, row_axes: tuple[str, ...]) -> None:
    """Raise for mesh shapes the row-sharded sparse path can't use."""
    for ax in row_axes:
        if ax not in mesh.shape:
            raise ValueError(
                f"row axis {ax!r} not in mesh axes {tuple(mesh.shape)}")
    bad = {ax: s for ax, s in mesh.shape.items()
           if ax not in row_axes and s != 1}
    if bad:
        raise ValueError(
            f"sparse=True shards the ELL graph over rows only "
            f"({row_axes!r}); every other mesh axis must have size 1, got "
            f"{bad}.  Reshape the mesh so all devices sit on the row axes "
            f"(e.g. (n_devices, 1) for a ('data', 'model') mesh).")


def _row_groups(mesh: Mesh, row_axes: tuple[str, ...]) -> int:
    g = 1
    for ax in row_axes:
        g *= mesh.shape[ax]
    return g


def shard_sparse_affinities(mesh: Mesh, row_axes: tuple[str, ...],
                            saff: SparseAffinities) -> ShardedSparseGraph:
    """Pad the ELL arrays to a row-group multiple and place them row-sharded.

    Padded rows get index 0 / weight 0 — a zero-weight edge contributes
    exactly zero to every operator, and index 0 keeps gathers in bounds.
    """
    validate_sparse_mesh(mesh, row_axes)
    g = saff.graph
    rev = saff.rev if saff.rev is not None else reverse_graph(g)
    n = g.n
    groups = _row_groups(mesh, row_axes)
    # per-shard rows rounded up to the hardware sublane multiple, so the
    # local-rows ELL kernel always has a legal, nb-dividing tile available
    nb = -(-n // groups)
    nb = -(-nb // 8) * 8
    n_pad = nb * groups
    spec = NamedSharding(mesh, P(row_axes, None))

    def pad_place(a, pad_value):
        a = jnp.pad(a, ((0, n_pad - n), (0, 0)),
                    constant_values=pad_value)
        return jax.device_put(a, spec)

    with span("graph-shard", phase=True, n=n, n_pad=n_pad, groups=groups):
        return ShardedSparseGraph(
            indices=pad_place(g.indices.astype(jnp.int32), 0),
            weights=pad_place(g.weights, 0),
            rev_indices=pad_place(rev.indices.astype(jnp.int32), 0),
            rev_weights=pad_place(rev.weights, 0),
            n=n, n_pad=n_pad,
        )


def _directed_lap_local(xi, Xp, idx, w):
    """Local rows of L(A) X: row gather from the replicated X — the
    per-shard, scatter-free form of kernels.ref.ell_lap_matvec_ref,
    accumulated through the shared core.objectives.directed_lap_apply so
    the sharded and single-device backends stay numerically identical."""
    return directed_lap_apply(w, xi, Xp[idx])


def _local_lap_fn(nb: int, k: int, n_rep: int, kernel_impl: str,
                  kernel_precision: str, kernel_lane: int):
    """(lap, kernel_active): the per-shard directed-Laplacian closure —
    either the jnp gather or the scalar-prefetch-translated Pallas kernel
    (kernels.ops.ell_lap_matvec_local).  Dispatch (autotune included)
    runs HERE, at build time, outside the shard_map trace; the closure
    traced inside the body carries only static config."""
    kw = ops.resolve_local_ell(nb, k, 0, impl=kernel_impl,
                               storage_dtype=kernel_precision, n_rep=n_rep,
                               lane=kernel_lane)
    if kw is None:
        return (lambda xi, Xp, idx, w, row0:
                _directed_lap_local(xi, Xp, idx, w)), False

    def lap(xi, Xp, idx, w, row0):
        return ops.ell_lap_matvec_local(Xp, idx, w, row0,
                                        lane=kernel_lane, **kw)

    return lap, True


def make_sharded_energy_grad(mesh: Mesh, row_axes: tuple[str, ...],
                             sg: ShardedSparseGraph, kind: str,
                             n_negatives: int | None = 5,
                             z_decay: float = 0.9,
                             kernel_impl: str = "auto",
                             kernel_precision: str = "float32",
                             kernel_lane: int = 128):
    """Jitted sharded energy/gradient closures for EVERY model family.

    Unnormalized kinds (ee/tee/epan): `eg(X, lam, key) -> (E, G)` and
    `e_only(X, lam, key) -> E` (the line-search fast path).

    Normalized kinds (ssne/tsne): `eg(X, lam, key, z_prev) -> (E, G, z)`
    threads the streaming partition-function estimate (the ratio estimator
    of core.objectives.energy_and_grad_sparse): each shard's partial Z is
    psum'd ONCE per application together with the attractive energy — one
    extra scalar riding the collective the unnormalized path already pays
    — and the EMA update runs replicated on the psum'd total, so every
    shard carries the identical z.  `e_only(X, lam, key) -> E` uses the
    instantaneous log(s_hat) and needs no state.

    Both closures numerically match the single-device
    `energy_and_grad_sparse` on the same graph, PRNG key and z_prev (same
    shift draw, same per-pair math; only partial-sum order differs).

    `kernel_impl`/`kernel_precision` select the per-shard Laplacian
    implementation (docs/kernels.md): with the local-rows Pallas kernel
    active the attractive symmetrization halves run through
    `kernels.ops.ell_lap_matvec_local` (dispatch + autotune resolved at
    build time, outside the shard_map trace) and the shard_map drops
    replication checking (`pallas_call` has no replication rule).
    """
    negative_pair_terms(kind, jnp.zeros(()))  # reject bad kinds at build
    normalized = is_normalized(kind)
    n, n_pad = sg.n, sg.n_pad
    exhaustive = n_negatives is None or n_negatives >= n - 1
    nb_shard = n_pad // _row_groups(mesh, row_axes)
    lap_local, kernel_active = _local_lap_fn(
        nb_shard, sg.indices.shape[1], n_pad, kernel_impl, kernel_precision,
        kernel_lane)
    smap = functools.partial(
        shard_map_norep if kernel_active else shard_map, mesh=mesh)

    # named_scope tags the per-shard epoch body in XLA/HLO metadata, so
    # `jax.profiler` traces (obs.Telemetry(jax_annotations=True)) attribute
    # device time to it; it is free outside of tracing
    @jax.named_scope("sharded-epoch")
    def body(with_grad, Xp, shifts, lam, scale, z_prev, idx, w, ridx, rw):
        nb = idx.shape[0]
        row0 = linear_row_index(row_axes) * nb
        xi = jax.lax.dynamic_slice_in_dim(Xp, row0, nb, 0)
        rows_g = row0 + jnp.arange(nb, dtype=jnp.int32)
        live = (rows_g < n).astype(Xp.dtype)[:, None]          # (nb, 1)

        # attractive: exact over the local ELL rows (t is symmetric, so the
        # directed sum needs no transpose pass for the energy); padded rows
        # have zero weights, so e_pair and aw vanish there
        xj = Xp[idx]                                           # (nb, k, d)
        diff = xi[:, None, :] - xj
        t_att = jnp.sum(diff * diff, axis=-1)
        e_pair, aw = attractive_edge_terms(kind, w, t_att)
        e_plus = jnp.sum(e_pair)

        # repulsive: cyclic-shift negatives at the global row ids
        J = (rows_g[:, None] + shifts[None, :]) % n            # (nb, m)
        t_neg = jnp.sum((xi[:, None, :] - Xp[J]) ** 2, axis=-1)
        s_pair, b = negative_pair_terms(kind, t_neg)
        s_hat = scale * jnp.sum(live * s_pair)

        # per-shard partials psum'd ONCE: e_plus and s_hat (the partial Z
        # for normalized kinds) share the collective
        tot = jax.lax.psum(jnp.stack([e_plus, s_hat]), row_axes)
        e_plus_g, s_hat_g = tot[0], tot[1]
        if normalized:
            E = e_plus_g + lam * jnp.log(s_hat_g)
            if exhaustive:
                z = s_hat_g             # exact Z: nothing left to smooth
            else:
                z = jnp.where(z_prev > 0,
                              z_decay * z_prev + (1.0 - z_decay) * s_hat_g,
                              s_hat_g)
        else:
            E = e_plus_g + lam * s_hat_g
            z = None
        if not with_grad:
            return E

        # both symmetrization halves as local gathers: A via the local
        # graph rows, A^T via the local reverse-graph rows.  For t-SNE the
        # X-dependent edge weight K = 1/(1+t) is a pure function of the
        # symmetric distance, so each half recomputes it from its own
        # local distances (same recipe as b_rev below).
        if kind == "tsne":
            arw = attractive_edge_terms(
                kind, rw,
                jnp.sum((xi[:, None, :] - Xp[ridx]) ** 2, axis=-1))[1]
            la_x = 0.5 * (lap_local(xi, Xp, idx, aw, row0)
                          + lap_local(xi, Xp, ridx, arw, row0))
        else:
            la_x = 0.5 * (lap_local(xi, Xp, idx, w, row0)
                          + lap_local(xi, Xp, ridx, rw, row0))

        # reverse negative half: the transpose of shift +s_j is shift -s_j
        # at the SAME per-edge weight, which is a pure function of the
        # symmetric distance — recompute it locally instead of fetching
        # b from the source row's shard
        b = live * b
        Jr = (rows_g[:, None] - shifts[None, :]) % n
        t_rev = jnp.sum((xi[:, None, :] - Xp[Jr]) ** 2, axis=-1)
        b_rev = live * negative_pair_terms(kind, t_rev)[1]
        lb_x = 0.5 * scale * (directed_lap_apply(b, xi, Xp[J])
                              + directed_lap_apply(b_rev, xi, Xp[Jr]))

        lam_rep = (lam / z) if normalized else lam
        G_loc = 4.0 * (la_x - lam_rep * lb_x)
        G = jnp.zeros_like(Xp)
        G = jax.lax.dynamic_update_slice_in_dim(G, G_loc, row0, 0)
        G = jax.lax.psum(G, row_axes)                          # O(N d) comm
        return (E, G, z) if normalized else (E, G)

    ell_specs = (P(row_axes, None),) * 4
    scalar_specs = (P(), P(), P(), P(), P())
    smap_eg = smap(
        functools.partial(body, True),
        in_specs=scalar_specs + ell_specs,
        out_specs=(P(), P(), P()) if normalized else (P(), P()),
    )
    smap_e = smap(
        functools.partial(body, False),
        in_specs=scalar_specs + ell_specs,
        out_specs=P(),
    )

    def _shifts(key, dtype):
        if exhaustive:
            return (jnp.arange(1, n, dtype=jnp.int32),
                    jnp.asarray(1.0, dtype))
        shifts = 1 + jax.random.choice(
            key, n - 1, shape=(n_negatives,), replace=False).astype(jnp.int32)
        return shifts, jnp.asarray((n - 1) / n_negatives, dtype)

    def _prep(X, lam, key):
        shifts, scale = _shifts(key, X.dtype)
        Xp = jnp.pad(X, ((0, n_pad - n), (0, 0)))
        return Xp, shifts, jnp.asarray(lam, X.dtype), scale

    ell_args = lambda: (sg.indices, sg.weights, sg.rev_indices,
                        sg.rev_weights)

    if normalized:
        @jax.jit
        def eg(X, lam, key, z_prev):
            E, Gp, z = smap_eg(*_prep(X, lam, key),
                               jnp.asarray(z_prev, X.dtype), *ell_args())
            return E, Gp[:n], z
    else:
        @jax.jit
        def eg(X, lam, key):
            E, Gp = smap_eg(*_prep(X, lam, key), jnp.zeros((), X.dtype),
                            *ell_args())
            return E, Gp[:n]

    @jax.jit
    def e_only(X, lam, key):
        return smap_e(*_prep(X, lam, key), jnp.zeros((), X.dtype),
                      *ell_args())

    return eg, e_only


def make_sharded_sd_operator(mesh: Mesh, row_axes: tuple[str, ...],
                             sg: ShardedSparseGraph,
                             saff: SparseAffinities,
                             mu_scale: float = 1e-5,
                             kernel_impl: str = "auto",
                             kernel_precision: str = "float32",
                             kernel_lane: int = 128):
    """(matvec, inv_diag, mu) for B = 4 L((A + Aᵀ)/2) + mu I with the
    Laplacian application row-sharded.

    The Jacobi diagonal and mu come from `sparse.linalg.make_sd_operator`
    on the UNSHARDED graph (a build-time scatter is fine), so the sharded
    CG solves the bit-identical system; only the single-device matvec is
    discarded.  The per-iteration matvec is shard_mapped: local gathers
    for both halves, one O(N d) psum to re-replicate.  This is the CG
    hot path — `kernel_impl`/`kernel_precision` put both halves on the
    local-rows Pallas kernel (dispatch resolved at build time, see
    `make_sharded_energy_grad`)."""
    _, inv_diag, mu = make_sd_operator(saff.graph, saff.rev, mu_scale)
    n, n_pad = sg.n, sg.n_pad
    nb_shard = n_pad // _row_groups(mesh, row_axes)
    lap_local, kernel_active = _local_lap_fn(
        nb_shard, sg.indices.shape[1], n_pad, kernel_impl, kernel_precision,
        kernel_lane)

    @jax.named_scope("sharded-sd-matvec")
    def body(Vp, idx, w, ridx, rw):
        nb = idx.shape[0]
        row0 = linear_row_index(row_axes) * nb
        vi = jax.lax.dynamic_slice_in_dim(Vp, row0, nb, 0)
        # 4 * 0.5 * (L(A) V + L(A^T) V)
        out_loc = 2.0 * (lap_local(vi, Vp, idx, w, row0)
                         + lap_local(vi, Vp, ridx, rw, row0))
        out = jnp.zeros_like(Vp)
        out = jax.lax.dynamic_update_slice_in_dim(out, out_loc, row0, 0)
        return jax.lax.psum(out, row_axes)

    smap = (shard_map_norep if kernel_active else shard_map)(
        body, mesh=mesh,
        in_specs=(P(),) + (P(row_axes, None),) * 4,
        out_specs=P(),
    )

    def matvec(V):
        Vp = jnp.pad(V, ((0, n_pad - n), (0, 0)))
        return (smap(Vp, sg.indices, sg.weights,
                     sg.rev_indices, sg.rev_weights)[:n] + mu * V)

    return matvec, inv_diag, mu
