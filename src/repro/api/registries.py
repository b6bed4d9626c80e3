"""Strategy and backend registries behind `repro.api.Embedding`.

The paper's point is that the partial-Hessian strategies are
*interchangeable* directions of one generic embedding formulation — so the
public API treats them as registry entries, not as hard-wired code paths:

  * the STRATEGY registry unifies `core/strategies.py` (dense partial-
    Hessian directions) with the sparse/sharded direction solvers (the
    matrix-free Jacobi-PCG spectral solve and its diagonal degenerations),
    so ``strategy="gd"|"fp"|"diag"|"sd"|"sd-"`` is one knob on every
    backend that supports it;
  * the BACKEND registry names the fitting paths grown over the
    previous PRs — ``dense`` (single device, fused jitted step),
    ``dense-mesh`` (2-D-sharded affinities + block-Jacobi), ``sparse``
    (ELL neighbor graph + negative sampling), ``sparse-sharded``
    (row-sharded ELL on a mesh) and ``tree`` (deterministic Barnes-Hut
    grid repulsion, opt-in) — plus ``backend="auto"``, which picks by
    problem size and device count (``tree`` stays opt-in: it is 2-D
    only and trades a little far-field bias for determinism).

Each strategy entry records which backends can realize it.  The dense
backend runs every strategy (it holds the full affinity matrix, so even
DiagH/SD- — which need dense Hessian terms — are available); the sparse
and mesh backends support the directions expressible over their storage:
the spectral direction (``sd``) and its diagonal degenerations (``fp``,
``gd``).  `resolve_backend` implements the ``auto`` policy and falls back
to ``dense`` when the size-preferred backend cannot run the requested
strategy, so ``EmbedSpec(strategy="sd-")`` never errors at auto-resolve
time.

Registration is open: `register_strategy` / `register_backend` let
downstream code add entries without touching this module (the built-in
``tree`` backend arrived exactly this way); `EmbedSpec` validation picks
the new names up automatically.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

from repro.core.baselines import LBFGS, NonlinearCG
from repro.core.strategies import SD, DiagH, FP, GD, SDMinus

#: N above which ``backend="auto"`` switches from the dense O(N^2) pipeline
#: to the sparse neighbor-graph pipeline (matches the spectral-init dense
#: cutoff in embed/trainer.py).
AUTO_SPARSE_N = 2048


# -- strategies -----------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StrategyEntry:
    """One registered search-direction strategy.

    `dense_factory(spec, **opts)` builds the `core/strategies` object used
    by the dense backend (the name -> object mapping is pinned bit-for-bit
    against a direct `fit_loop` in tests/test_api.py).  The
    sparse/mesh realizations live in the backends themselves
    (embed/trainer.py), keyed by the canonical name.
    """

    name: str
    backends: frozenset[str]
    dense_factory: Callable[..., Any]
    default_ls_init: str = "one"   # LSConfig.init_step when EmbedSpec.ls=None
    doc: str = ""


STRATEGIES: dict[str, StrategyEntry] = {}
_STRATEGY_ALIASES: dict[str, str] = {}


def register_strategy(name: str, *, backends, dense_factory,
                      default_ls_init: str = "one", aliases=(),
                      doc: str = "") -> None:
    STRATEGIES[name] = StrategyEntry(
        name=name, backends=frozenset(backends),
        dense_factory=dense_factory, default_ls_init=default_ls_init,
        doc=doc)
    for a in aliases:
        _STRATEGY_ALIASES[a] = name


def available_strategies() -> list[str]:
    return sorted(STRATEGIES)


def canonical_strategy(name: str) -> str:
    """Canonical registry name (resolving aliases), or ValueError listing
    the valid names — the early-validation error `EmbedSpec` surfaces at
    construction."""
    low = name.lower()
    low = _STRATEGY_ALIASES.get(low, low)
    if low not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {name!r}; registered strategies: "
            f"{available_strategies()}")
    return low


def strategy_entry(name: str) -> StrategyEntry:
    return STRATEGIES[canonical_strategy(name)]


# -- backends -------------------------------------------------------------------


@dataclasses.dataclass
class BackendEntry:
    """One registered fitting path.  `fit` is attached lazily by
    `repro.api.backends` (which imports the heavy trainer machinery); the
    name/doc/needs_mesh metadata is available as soon as this module
    imports, so spec validation never pays the import."""

    name: str
    doc: str = ""
    needs_mesh: bool = False
    fit: Callable[..., Any] | None = None


BACKENDS: dict[str, BackendEntry] = {}


def register_backend(name: str, *, doc: str = "", needs_mesh: bool = False,
                     fit=None) -> None:
    BACKENDS[name] = BackendEntry(name=name, doc=doc, needs_mesh=needs_mesh,
                                  fit=fit)


def attach_backend_impl(name: str, fit) -> None:
    """Attach the fit callable to an already-registered backend — the one
    registration point for name/doc/needs_mesh stays in this module;
    `repro.api.backends` only supplies the implementations."""
    BACKENDS[name].fit = fit


def available_backends() -> list[str]:
    return sorted(BACKENDS)


def validate_backend(name: str) -> str:
    if name != "auto" and name not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{available_backends()} (or 'auto')")
    return name


def validate_strategy_backend(strategy: str, backend: str) -> None:
    entry = strategy_entry(strategy)
    if backend != "auto" and backend not in entry.backends:
        raise ValueError(
            f"strategy {entry.name!r} is not available on backend "
            f"{backend!r}; it runs on {sorted(entry.backends)} "
            f"(every strategy runs on 'dense')")


def backend_impl(name: str):
    """The backend's fit callable, importing `repro.api.backends` on first
    use (which attaches the implementations to the registry)."""
    entry = BACKENDS[validate_backend(name)]
    if entry.fit is None:
        import repro.api.backends  # noqa: F401  (registers implementations)
        entry = BACKENDS[name]
    if entry.fit is None:  # pragma: no cover - a registration bug
        raise RuntimeError(f"backend {name!r} has no implementation attached")
    return entry.fit


def resolve_backend(backend: str, *, n: int, n_devices: int,
                    strategy: str) -> str:
    """``auto`` policy: sparse above AUTO_SPARSE_N points, mesh-sharded
    when more than one device is visible; falls back to ``dense`` when the
    size-preferred backend cannot realize the requested strategy, or when
    the dense-mesh (N, N) sharding needs N divisible by the device count
    and it isn't (the sparse-sharded backend pads rows instead)."""
    if backend != "auto":
        return validate_backend(backend)
    multi = n_devices > 1
    if n > AUTO_SPARSE_N:
        name = "sparse-sharded" if multi else "sparse"
    else:
        name = "dense-mesh" if multi and n % n_devices == 0 else "dense"
    if name not in strategy_entry(strategy).backends:
        name = "dense"               # every registered strategy runs dense
    return name


# -- built-in registrations -----------------------------------------------------

_ALL_BACKENDS = ("dense", "dense-mesh", "sparse", "sparse-sharded", "tree")

register_backend("dense", doc="single device, full affinities, fused "
                              "jitted step (core/minimize.py)")
register_backend("dense-mesh", needs_mesh=True,
                 doc="2-D-sharded affinities + block-Jacobi solves "
                     "(embed/trainer.py)")
register_backend("sparse", doc="ELL neighbor graph + negative sampling, "
                               "Jacobi-PCG (docs/sparse.md)")
register_backend("sparse-sharded", needs_mesh=True,
                 doc="row-sharded ELL graph, replicated-X epochs "
                     "(sparse/sharding.py)")
register_backend("tree", doc="deterministic Barnes-Hut grid repulsion, "
                             "O(N log N), 2-D only (docs/farfield.md)")

register_strategy(
    "gd", backends=_ALL_BACKENDS,
    dense_factory=lambda spec, **o: GD(**o),
    doc="gradient descent: B = I")
register_strategy(
    "fp", backends=_ALL_BACKENDS,
    dense_factory=lambda spec, **o: FP(**o),
    doc="diagonal fixed-point: B = 4 D+ (x) I_d")
register_strategy(
    "diag", backends=("dense",), aliases=("diagh",),
    dense_factory=lambda spec, **o: DiagH(**o),
    doc="clipped diagonal of the full Hessian (needs dense terms)")
register_strategy(
    "sd", backends=_ALL_BACKENDS, default_ls_init="adaptive_grow",
    dense_factory=lambda spec, **o: SD(**{"mu_scale": spec.mu_scale, **o}),
    doc="the spectral direction: B = 4 L+ + mu I (paper headline)")
register_strategy(
    "sd-", backends=("dense",), aliases=("sdminus",),
    default_ls_init="adaptive_grow",
    dense_factory=lambda spec, **o: SDMinus(**o),
    doc="SD plus psd repulsive curvature blocks (batched CG)")
# quasi-Newton baselines from the paper's comparison lineup, so benchmark
# drivers route every method through the one estimator surface
register_strategy(
    "lbfgs", backends=("dense",), aliases=("l-bfgs",),
    dense_factory=lambda spec, **o: LBFGS(**o),
    doc="limited-memory BFGS baseline")
register_strategy(
    "cg", backends=("dense",), aliases=("nonlinearcg",),
    dense_factory=lambda spec, **o: NonlinearCG(**o),
    doc="nonlinear conjugate-gradient baseline")
