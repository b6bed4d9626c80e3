"""Mesh-aware `Objective` backends for the unified fit engine, plus the
legacy `DistributedEmbedding`/`EmbedConfig` entry points (now thin
deprecation shims over `repro.api.Embedding`).

The optimization loop lives in embed/engine.py (`fit_loop`); this module
contributes the backend builders the public API composes:

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

Both builders take a `strategy` name (the `repro.api` strategy registry):
the spectral direction (``sd``, the default) plus its diagonal
degenerations ``fp`` (B = 4 D+ + mu I — the paper's fixed-point iteration,
realized here from the same degree vector that Jacobi-preconditions the
sparse CG) and ``gd`` (B = I).  Strategies that need dense Hessian terms
(``diag``, ``sd-``) are dense-backend-only and rejected by the registry
before a builder ever runs.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

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
from .engine import EngineResult, LoopConfig

Array = jnp.ndarray


@dataclasses.dataclass
class EmbedConfig:
    """DEPRECATED: use `repro.api.EmbedSpec` (declarative spec with
    strategy/backend registries).  Kept as a validating shim: unknown
    `kind`/`strategy` fail at construction with the registry's valid
    names, and `DistributedEmbedding` converts to an `EmbedSpec`."""

    kind: str = "ee"
    lam: float = 100.0
    perplexity: float = 20.0
    dim: int = 2
    max_iters: int = 200
    tol: float = 1e-7
    mu_scale: float = 1e-5
    strategy: str = "sd"
    ls: LSConfig = dataclasses.field(
        default_factory=lambda: LSConfig(init_step="adaptive_grow")
    )
    checkpoint_dir: str | None = None
    checkpoint_every: int = 50
    seed: int = 0
    # sparse neighbor-graph pipeline (docs/sparse.md)
    sparse: bool = False
    n_neighbors: int = 0         # ELL width k; 0 => auto (3 * perplexity).
                                 # k < perplexity is rejected: the k-candidate
                                 # entropy can't reach log(perplexity) and the
                                 # calibration would degenerate to uniform.
    n_negatives: int = 5         # uniform negative samples per point
    z_ema_decay: float = 0.9     # streaming partition-function EMA for the
                                 # normalized kinds' sparse ratio estimator
                                 # (0 disables smoothing; ignored when the
                                 # negatives are exhaustive)
    knn_method: str = "auto"     # 'exact' | 'approx' | 'auto'
    cg_tol: float = 1e-3
    cg_maxiter: int = 100

    def __post_init__(self):
        # early validation through the api registries (deferred import:
        # repro.api.backends imports this module)
        from repro.api.registries import canonical_strategy
        from repro.api.spec import validate_kind

        validate_kind(self.kind)
        self.strategy = canonical_strategy(self.strategy)
        warnings.warn(
            "EmbedConfig is deprecated; use repro.api.EmbedSpec "
            "(strategy/backend registries, one spec for every backend)",
            DeprecationWarning, stacklevel=2)

    def to_spec(self, n_devices: int = 1):
        """The equivalent `repro.api.EmbedSpec` (sparse flag -> backend)."""
        from repro.api.spec import EmbedSpec

        if self.sparse:
            backend = "sparse-sharded" if n_devices > 1 else "sparse"
        else:
            backend = "dense-mesh"
        return EmbedSpec(
            kind=self.kind, strategy=self.strategy, backend=backend,
            lam=self.lam, perplexity=self.perplexity, dim=self.dim,
            max_iters=self.max_iters, tol=self.tol, mu_scale=self.mu_scale,
            ls=self.ls, checkpoint_dir=self.checkpoint_dir,
            checkpoint_every=self.checkpoint_every, seed=self.seed,
            n_neighbors=self.n_neighbors, n_negatives=self.n_negatives,
            z_ema_decay=self.z_ema_decay, knn_method=self.knn_method,
            cg_tol=self.cg_tol, cg_maxiter=self.cg_maxiter)


@dataclasses.dataclass
class FitResult:
    X: Array
    energies: np.ndarray
    times: np.ndarray
    n_iters: int
    resumed_from: int | None
    diagnostics: list[dict] | None = None   # per-iteration table when the
                                            # run was fit with telemetry /
                                            # a diagnostics consumer


def to_fit_result(res: EngineResult) -> FitResult:
    return FitResult(X=res.X, energies=res.energies, times=res.times,
                     n_iters=res.n_iters, resumed_from=res.resumed_from,
                     diagnostics=res.diagnostics)


def make_loop_config(cfg, ls: LSConfig) -> LoopConfig:
    """LoopConfig from any spec-shaped config (EmbedSpec or EmbedConfig)."""
    return LoopConfig(
        max_iters=cfg.max_iters, tol=cfg.tol, ls=ls,
        checkpoint_dir=cfg.checkpoint_dir,
        checkpoint_every=cfg.checkpoint_every, seed=cfg.seed,
        max_seconds=getattr(cfg, "max_seconds", None),
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

    # kernel-dispatch knobs (EmbedSpec; legacy EmbedConfig has neither,
    # so getattr keeps the deprecation shims byte-identical)
    kernel_impl = getattr(cfg, "kernel_impl", "auto")
    kernel_precision = getattr(cfg, "kernel_precision", "float32")
    kernel_args = cfg.kernel_args() if hasattr(cfg, "kernel_args") else {}

    if sharded:
        sg = shard_sparse_affinities(mesh, mspec.row_axes, saff)
        eg_l, e_l = make_sharded_energy_grad(
            mesh, mspec.row_axes, sg, cfg.kind,
            n_negatives=cfg.n_negatives, z_decay=cfg.z_ema_decay,
            kernel_impl=kernel_impl, kernel_precision=kernel_precision)
        if normalized:
            eg = lambda X, key, z: eg_l(X, lam, key, z)
        else:
            eg = lambda X, key: eg_l(X, lam, key)
        e_only = lambda X, key: e_l(X, lam, key)
        matvec, inv_diag, _ = make_sharded_sd_operator(
            mesh, mspec.row_axes, sg, saff, cfg.mu_scale,
            kernel_impl=kernel_impl, kernel_precision=kernel_precision)
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

    plan = make_grid_plan(
        n, theta=cfg.theta, depth=getattr(cfg, "tree_depth", 0),
        cap=getattr(cfg, "tree_cap", 0))
    kernel_args = cfg.kernel_args() if hasattr(cfg, "kernel_args") else {}

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


class DistributedEmbedding:
    """DEPRECATED: use `repro.api.Embedding` (pass the mesh to its
    constructor).  Thin shim: converts the `EmbedConfig` to an `EmbedSpec`
    and delegates `fit` to the estimator, so legacy call sites keep their
    exact behavior (same builders, same engine, same results)."""

    def __init__(self, cfg: EmbedConfig, mesh: Mesh,
                 spec: EmbedMeshSpec | None = None):
        warnings.warn(
            "DistributedEmbedding is deprecated; use repro.api.Embedding "
            "(EmbedSpec + mesh) instead",
            DeprecationWarning, stacklevel=2)
        self.cfg = cfg
        self.mesh = mesh
        self.spec = spec if spec is not None else default_mesh_spec(mesh)

    def fit(self, Y: Array, X0: Array | None = None,
            callback: Callable[[int, Array, float], None] | None = None
            ) -> FitResult:
        from repro.api import Embedding

        est = Embedding(self.cfg.to_spec(self.mesh.devices.size),
                        mesh=self.mesh, mesh_spec=self.spec)
        est.fit(Y, X0=X0, callback=callback)
        return to_fit_result(est.result_)
