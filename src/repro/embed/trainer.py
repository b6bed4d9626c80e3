"""Mesh-aware and matrix-free `Objective` backends for the unified fit
engine.

The optimization loop lives in embed/engine.py (`fit_loop`); this module
contributes the backend builders the public API (`repro.api.backends`)
composes, each from a `repro.api.EmbedSpec` (`cfg` below):

  * `build_dense_mesh_objective` — the N x N affinities 2-D sharded; the
    spectral direction is solved block-Jacobi (DESIGN.md §3.4).  On a
    single device the same code runs with a (1, 1) mesh, which is how the
    CPU tests exercise every code path.
  * `build_sparse_objective` — the O(N (k + m) d) neighbor-graph pipeline
    (docs/sparse.md): k-NN affinities in ELL storage, negative-sampled
    repulsion, matrix-free direction solves; no (N, N) array anywhere.
    Normalized models (ssne/tsne) run through the sampled ratio estimator
    for the partition function, with a streaming (EMA) Z estimate threaded
    through the objective and checkpointed so resumed runs stay
    bit-identical.  With `sharded=True` the same pipeline row-shards the
    ELL graph + reverse graph over the mesh (sparse/sharding.py); mesh
    shapes the sparse path can't use (a >1-sized column axis) are rejected
    with a clear error.

The builders take a `strategy` name (the `repro.api` strategy registry):
the spectral direction (``sd``, the default) plus its diagonal
degenerations ``fp`` (B = 4 D+ + mu I — the paper's fixed-point iteration,
realized here from the same degree vector that Jacobi-preconditions the
sparse CG) and ``gd`` (B = I).  Strategies that need dense Hessian terms
(``diag``, ``sd-``) are dense-backend-only and rejected by the registry
before a builder ever runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import (energy_and_grad_sparse, is_normalized,
                        laplacian_eigenmaps, make_affinities)
from repro.core.laplacian import degree
from repro.core.linesearch import LSConfig
from repro.core.objectives import attractive_weights
from repro.core.strategies import _jitter
from repro.obs import block_if_traced, span
from repro.sparse import (energy_and_grad_tree, make_grid_plan,
                          make_sd_operator, make_sharded_energy_grad,
                          make_sharded_sd_operator, pcg,
                          shard_sparse_affinities, sparse_affinities,
                          sparse_laplacian_eigenmaps, to_dense,
                          tree_diagnostics, validate_sparse_mesh)

from .distributed import (
    EmbedMeshSpec,
    make_block_jacobi_setup,
    make_block_jacobi_solve,
    make_distributed_energy_grad,
    replicate,
    shard_pairwise,
    shard_rows,
)
from .engine import LoopConfig

Array = jnp.ndarray


def make_loop_config(spec, ls: LSConfig) -> LoopConfig:
    """The engine's LoopConfig for a `repro.api.EmbedSpec`."""
    return LoopConfig(
        max_iters=spec.max_iters, tol=spec.tol, ls=ls,
        checkpoint_dir=spec.checkpoint_dir,
        checkpoint_every=spec.checkpoint_every, seed=spec.seed,
        max_seconds=spec.max_seconds,
    )


def default_mesh_spec(mesh: Mesh) -> EmbedMeshSpec:
    names = mesh.axis_names
    return EmbedMeshSpec(row_axes=tuple(names[:-1]) or (names[0],),
                         col_axis=names[-1])


class _DenseMeshObjective:
    """Dense 2-D-sharded backend: distributed energy/grad + a pluggable
    direction solve.  Deterministic (key is ignored)."""

    stochastic = False

    def __init__(self, mesh, eg, solver_factory, place):
        self._mesh = mesh
        self._eg = eg
        self._solver_factory = solver_factory
        self._place = place

    def energy_and_grad(self, X, key):
        return self._eg(X)

    def energy(self, X, key):
        return self._eg(X)[0]

    def make_direction_solver(self):
        return self._solver_factory()

    def place(self, X):
        return self._place(X)


class _SparseObjective:
    """Sparse backend over prebuilt jitted (eg, e_only, direction-solve)
    closures; identical shape for the single-device and row-sharded
    variants.  Stochastic: the engine draws one fold_in key per iteration,
    so the line search descends a deterministic surrogate (common random
    numbers) and convergence is tested on an EMA of the surrogate
    energies.  `solve(G, P0) -> (P, diag)` may use P0 as a warm start (the
    PCG spectral direction does; the diagonal strategies ignore it);
    `diag` is a dict of device scalars the solver computed anyway (PCG
    iteration count, final relative residual) — kept on the objective and
    surfaced host-side through `diagnostics()` so the engine's telemetry
    records solver quality, not just wall-clock."""

    stochastic = True

    def __init__(self, eg, e_only, solve, X0, place=None):
        self._eg, self._e_only, self._solve = eg, e_only, solve
        self._X0 = X0
        self._place = place
        self._solver_diag: dict = {}

    def energy_and_grad(self, X, key):
        return self._eg(X, key)

    def energy(self, X, key):
        return self._e_only(X, key)

    def make_direction_solver(self):
        def solve(prev_P, X, G):
            P, self._solver_diag = self._solve(G, jnp.asarray(prev_P))
            return P, P                                # CG warm start

        return solve, jnp.zeros_like(self._X0)

    def diagnostics(self) -> dict:
        """Host floats of the last direction solve's diagnostics (only
        called when telemetry or a diagnostics consumer is attached, so
        the device->host transfer is never paid by plain fits)."""
        # one batched transfer instead of a sync per scalar (RPR001)
        host = jax.device_get(self._solver_diag)
        return {k: float(v) for k, v in host.items()}

    def place(self, X):
        return self._place(X) if self._place is not None else X


class _NormalizedSparseObjective(_SparseObjective):
    """Sparse backend for the normalized models (ssne/tsne): threads the
    streaming partition-function estimate z through the ratio-estimator
    closures — `eg(X, key, z) -> (E, G, z_new)` — and exposes it to the
    engine's checkpoint payload (carry_state/restore_carry) so a resumed
    run replays the uninterrupted gradient trajectory bit-for-bit.  The
    energy itself uses the instantaneous estimate (no state), so the
    line-search fast path `e_only(X, key)` is unchanged in shape."""

    def __init__(self, eg, e_only, solve, X0, place=None):
        super().__init__(eg, e_only, solve, X0, place=place)
        # z <= 0 means uninitialized: the first application uses its own
        # instantaneous estimate (see energy_and_grad_sparse)
        self._z = jnp.zeros((), X0.dtype)

    def energy_and_grad(self, X, key):
        E, G, self._z = self._eg(X, key, self._z)
        return E, G

    def carry_state(self):
        return np.asarray(self._z)

    def restore_carry(self, z):
        self._z = jnp.asarray(z)

    def diagnostics(self) -> dict:
        # batch z with the solver diagnostics in one transfer (RPR001)
        host = jax.device_get({**self._solver_diag, "z_ema": self._z})
        return {k: float(v) for k, v in host.items()}


class _TreeObjective(_SparseObjective):
    """Deterministic Barnes-Hut backend (sparse/farfield.py): same closure
    shape as the sparse objective, but nothing is sampled — the engine's
    deterministic path applies (no per-iteration key, the accepted
    energy is reused instead of re-evaluated, checkpoint resume is
    bit-identical without carried estimator state).  `diagnostics()`
    adds the grid decomposition health (cells visited, realized opening
    ratio, residual spill, the pair-partition invariant) computed lazily
    from the last evaluated X — only paid when telemetry is attached."""

    stochastic = False

    def __init__(self, eg, e_only, solve, X0, plan, place=None):
        super().__init__(eg, e_only, solve, X0, place=place)
        self._plan = plan
        self._last_X = X0

    def energy_and_grad(self, X, key):
        self._last_X = X
        return self._eg(X, key)

    def diagnostics(self) -> dict:
        tree = tree_diagnostics(self._last_X, self._plan)
        # batch grid health with the solver diagnostics (RPR001)
        host = jax.device_get({**self._solver_diag, **tree})
        return {k: float(v) for k, v in host.items()}


# -- backend builders -----------------------------------------------------------


def build_dense_mesh_objective(cfg, mesh: Mesh,
                               mspec: EmbedMeshSpec | None = None,
                               Y: Array | None = None,
                               X0: Array | None = None,
                               strategy: str = "sd"):
    """(objective, X) for the dense 2-D-sharded backend.

    Strategies: ``sd`` (block-Jacobi Cholesky per row-block — the sharded
    realization of the spectral direction), ``fp`` (B = 4 D+ + mu I with
    the full degree vector, computed once from the dense affinities before
    they are sharded), ``gd``.
    """
    if mspec is None:
        mspec = default_mesh_spec(mesh)
    with span("graph-build", phase=True, n=Y.shape[0], dense=True):
        aff = jax.block_until_ready(
            make_affinities(jnp.asarray(Y), cfg.perplexity, model=cfg.kind))
    if X0 is not None:
        X = jnp.asarray(X0)
    else:
        with span("spectral-init", phase=True, n=Y.shape[0]):
            X = block_if_traced(laplacian_eigenmaps(aff.Wp, cfg.dim) * 0.1)
    lam = jnp.asarray(cfg.lam, X.dtype)

    # W- == 1 off-diagonal for every supported affinity builder: use the
    # storage-free repulsion path (2x less O(N^2) state and traffic)
    eg_unit = make_distributed_energy_grad(mesh, mspec, cfg.kind,
                                           unit_wm=True)
    Wp = shard_pairwise(mesh, mspec, aff.Wp)
    eg = lambda X: eg_unit(X, Wp, lam)
    place = lambda X: replicate(mesh, X)

    if strategy == "sd":
        bj_setup = make_block_jacobi_setup(mesh, mspec, cfg.mu_scale)
        bj_solve = make_block_jacobi_solve(mesh, mspec)

        def solver_factory():
            R = bj_setup(Wp)                     # block-Jacobi factors

            def solve(state, X, G):
                G_sh = shard_rows(mesh, mspec, G)
                return replicate(mesh, bj_solve(R, G_sh)), state

            return solve, ()
    elif strategy == "fp":
        dp = degree(attractive_weights(aff, cfg.kind))
        inv_diag = 1.0 / (4.0 * dp + _jitter(jnp.min(dp), jnp.mean(dp)))

        def solver_factory():
            def solve(state, X, G):
                return -inv_diag[:, None] * G, state

            return solve, ()
    elif strategy == "gd":
        def solver_factory():
            return (lambda state, X, G: (-G, state)), ()
    else:
        raise ValueError(
            f"strategy {strategy!r} is not available on the dense-mesh "
            f"backend (have 'sd', 'fp', 'gd')")

    obj = _DenseMeshObjective(mesh, eg, solver_factory, place)
    return obj, place(X)


def _sparse_spectral_init(cfg, saff, n: int) -> Array:
    """Spectral init: dense eigendecomposition while affordable, block
    power iteration on the ELL graph above that (sparse/linalg.py)."""
    if n <= 2048:
        A = to_dense(saff.graph)
        return laplacian_eigenmaps(0.5 * (A + A.T), cfg.dim) * 0.1
    return sparse_laplacian_eigenmaps(
        saff.graph, saff.rev, d=cfg.dim, seed=cfg.seed) * 0.1


def _resolve_saff(cfg, Y, saff, n: int):
    """The calibrated ELL affinities: the caller's precomputed `saff`
    when given (the `fit(saff=...)` path — strategy/backend sweeps share
    one k-NN build), else built from Y."""
    if saff is not None:
        if saff.graph.n != n:
            raise ValueError(
                f"precomputed saff has {saff.graph.n} rows but the fit "
                f"is over n={n} points")
        return saff
    k = cfg.n_neighbors or min(int(3 * cfg.perplexity), n - 1)
    if k < cfg.perplexity:
        raise ValueError(
            f"n_neighbors={k} < perplexity={cfg.perplexity}: the "
            f"k-candidate entropy cannot reach log(perplexity), so the "
            f"calibration would silently degenerate to uniform weights; "
            f"use n_neighbors >= 3 * perplexity (or 0 for auto)")
    return sparse_affinities(jnp.asarray(Y), k=k,
                             perplexity=cfg.perplexity, model=cfg.kind,
                             method=cfg.knn_method)


def _make_direction_solve(strategy: str, matvec, inv_diag, cfg,
                          backend: str):
    """The jitted `solve(G, P0) -> (P, diag)` closure shared by the
    matrix-free backends (sparse, sparse-sharded, tree): Jacobi-PCG on
    B = 4 L(W+) + mu I for ``sd``, its diagonal for ``fp``, identity for
    ``gd``."""
    if strategy == "sd":
        @jax.jit
        def solve(G, P0):
            # surface the PCG counters the solver computes anyway — two
            # extra scalar outputs, no extra work in the jitted program
            with jax.named_scope("direction-solve"):
                r = pcg(matvec, -G, P0, inv_diag=inv_diag,
                        tol=cfg.cg_tol, maxiter=cfg.cg_maxiter)
            return r.x, {"pcg_iters": r.n_iters,
                         "pcg_residual": r.rel_residual}
        return solve
    if strategy == "fp":
        return jax.jit(lambda G, P0: (-inv_diag[:, None] * G, {}))
    if strategy == "gd":
        return jax.jit(lambda G, P0: (-G, {}))
    raise ValueError(
        f"strategy {strategy!r} is not available on the {backend} "
        f"backends (have 'sd', 'fp', 'gd')")


def build_sparse_objective(cfg, mesh: Mesh | None = None,
                           mspec: EmbedMeshSpec | None = None,
                           Y: Array | None = None,
                           X0: Array | None = None,
                           strategy: str = "sd",
                           sharded: bool = False,
                           saff=None):
    """(objective, X) for the sparse neighbor-graph backend, O(N (k + m) d)
    per iteration: ELL affinities, negative-sampled repulsion, matrix-free
    direction solves.  `sharded=True` row-shards the graph over the mesh
    (sparse/sharding.py).  A precomputed `saff` (sparse.SparseAffinities)
    skips the k-NN build — the `fit(saff=...)` path.

    Strategies: ``sd`` (Jacobi-PCG on B = 4 L(W+) + mu I, warm-started),
    ``fp`` (the SAME system's Jacobi diagonal applied directly — B's exact
    inverse restricted to its diagonal 4 D+ + mu, the paper's fixed-point
    iteration over the sparse graph) and ``gd``.
    """
    normalized = is_normalized(cfg.kind)
    n = Y.shape[0] if Y is not None else saff.graph.n
    if sharded:
        if mesh is None:
            raise ValueError("the sparse-sharded backend needs a mesh")
        if mspec is None:
            mspec = default_mesh_spec(mesh)
        # fail fast on unusable mesh shapes, before the k-NN build
        validate_sparse_mesh(mesh, mspec.row_axes)
    lam = jnp.asarray(cfg.lam, jnp.float32)
    saff = _resolve_saff(cfg, Y, saff, n)
    if X0 is not None:
        X = jnp.asarray(X0)
    else:
        with span("spectral-init", phase=True, n=n):
            X = jax.block_until_ready(_sparse_spectral_init(cfg, saff, n))

    kernel_args = cfg.kernel_args()

    if sharded:
        sg = shard_sparse_affinities(mesh, mspec.row_axes, saff)
        eg_l, e_l = make_sharded_energy_grad(
            mesh, mspec.row_axes, sg, cfg.kind,
            n_negatives=cfg.n_negatives, z_decay=cfg.z_ema_decay,
            kernel_impl=cfg.kernel_impl,
            kernel_precision=cfg.kernel_precision)
        if normalized:
            eg = lambda X, key, z: eg_l(X, lam, key, z)
        else:
            eg = lambda X, key: eg_l(X, lam, key)
        e_only = lambda X, key: e_l(X, lam, key)
        matvec, inv_diag, _ = make_sharded_sd_operator(
            mesh, mspec.row_axes, sg, saff, cfg.mu_scale,
            kernel_impl=cfg.kernel_impl,
            kernel_precision=cfg.kernel_precision)
        place = lambda X: replicate(mesh, X)
        X = place(X)
    else:
        # SparseSD's Laplacian system is model-independent (the paper
        # freezes the attractive Hessian at X = 0, where every kernel's
        # -K'(0) = 1), so normalized kinds reuse the same CG operator.
        # The matvec is the CG hot path: kernel_args routes it through
        # the Pallas dispatcher (vmem or HBM layout, bf16 storage)
        matvec, inv_diag, _ = make_sd_operator(saff.graph, saff.rev,
                                               cfg.mu_scale, **kernel_args)

        # device scope `objective` on every evaluation (op metadata only)
        if normalized:
            @jax.jit
            def eg(X, key, z):
                with jax.named_scope("objective"):
                    return energy_and_grad_sparse(
                        X, saff, cfg.kind, lam,
                        n_negatives=cfg.n_negatives, key=key, z_prev=z,
                        z_decay=cfg.z_ema_decay, return_state=True)
        else:
            @jax.jit
            def eg(X, key):
                with jax.named_scope("objective"):
                    return energy_and_grad_sparse(
                        X, saff, cfg.kind, lam,
                        n_negatives=cfg.n_negatives, key=key)

        @jax.jit
        def e_only(X, key):
            # line-search trials need no gradient: ~half the work
            with jax.named_scope("objective"):
                return energy_and_grad_sparse(
                    X, saff, cfg.kind, lam, n_negatives=cfg.n_negatives,
                    key=key, with_grad=False)[0]

        place = None

    solve = _make_direction_solve(strategy, matvec, inv_diag, cfg, "sparse")
    obj_cls = _NormalizedSparseObjective if normalized else _SparseObjective
    return obj_cls(eg, e_only, solve, X, place=place), X


def build_tree_objective(cfg, Y: Array | None = None,
                         X0: Array | None = None,
                         strategy: str = "sd",
                         saff=None):
    """(objective, X) for the deterministic Barnes-Hut backend
    (sparse/farfield.py): exact ELL attractive terms + grid far-field
    repulsion under the `cfg.theta` opening criterion.  O(N log N) per
    iteration, no PRNG or EMA anywhere — repeated fits are bit-identical.
    2-D embeddings only (the grid is a quadtree); the direction solves
    are the same matrix-free sd/fp/gd family as the sparse backend (the
    spectral system only sees the attractive graph)."""
    if cfg.dim != 2:
        raise ValueError(
            f"the tree backend is 2-D only (quadtree far field); "
            f"got dim={cfg.dim} — use the sparse backend for other dims")
    n = Y.shape[0] if Y is not None else saff.graph.n
    lam = jnp.asarray(cfg.lam, jnp.float32)
    saff = _resolve_saff(cfg, Y, saff, n)
    if X0 is not None:
        X = jnp.asarray(X0)
    else:
        with span("spectral-init", phase=True, n=n):
            X = jax.block_until_ready(_sparse_spectral_init(cfg, saff, n))

    plan = make_grid_plan(n, theta=cfg.theta, depth=cfg.tree_depth,
                          cap=cfg.tree_cap)
    kernel_args = cfg.kernel_args()

    def eg(X, key):
        return energy_and_grad_tree(X, saff, lam, cfg.kind, plan,
                                    **kernel_args)

    def e_only(X, key):
        return energy_and_grad_tree(X, saff, lam, cfg.kind, plan,
                                    with_grad=False, **kernel_args)[0]

    matvec, inv_diag, _ = make_sd_operator(saff.graph, saff.rev,
                                           cfg.mu_scale, **kernel_args)
    solve = _make_direction_solve(strategy, matvec, inv_diag, cfg, "tree")
    obj = _TreeObjective(eg, e_only, solve, X, plan)
    return obj, X
