"""Backend implementations for the `repro.api` registry.

Each backend is a function `fit(spec, Y, *, X0, aff, saff, mesh,
mesh_spec, callback, telemetry) -> EngineResult` composing an `Objective`
(core/minimize.py `DenseObjective` or the embed/trainer.py builders) with
the unified engine (`embed.engine.fit_loop`).

Precomputed inputs: `aff=` (dense `core.Affinities`) is dense-backend-
only; `saff=` (sparse `SparseAffinities`) is the neighbor-graph analogue
for the sparse/tree backends, letting strategy sweeps share one k-NN
build.  Each backend rejects the other family's input with a pointed
error instead of silently ignoring it.

Telemetry: each backend activates `telemetry.tracer` around *both* the
objective build (so graph-build / spectral-init spans land in the trace;
on the dense path they block on their outputs only while traced) and the
fit loop, then hands the `Telemetry` on to `fit_loop` which wires
its `RunRecorder` into the iteration stream.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from repro.core import laplacian_eigenmaps, make_affinities
from repro.core.minimize import DenseObjective
from repro.embed.engine import fit_loop
from repro.embed.trainer import (build_dense_mesh_objective,
                                 build_sparse_objective,
                                 build_tree_objective, make_loop_config)
from repro.obs import activate, block_if_traced, span

from .registries import attach_backend_impl, strategy_entry


def _tracing(telemetry):
    if telemetry is None:
        return contextlib.nullcontext()
    return activate(telemetry.tracer)


def _reject_saff(saff, backend: str):
    if saff is not None:
        raise ValueError(
            f"precomputed saff= is for the sparse/tree backends (the "
            f"{backend} backend computes dense affinities; pass aff= "
            f"instead)")


def _dense_problem(spec, Y, X0, aff):
    if aff is None:
        if Y is None:
            raise ValueError("fit needs Y (or a precomputed aff=)")
        with span("graph-build", phase=True, dense=True):
            aff = block_if_traced(make_affinities(
                jnp.asarray(Y), spec.perplexity, model=spec.kind))
    if X0 is None:
        with span("spectral-init", phase=True):
            X0 = block_if_traced(laplacian_eigenmaps(aff.Wp, spec.dim) * 0.1)
    return aff, jnp.asarray(X0)


def fit_dense(spec, Y, *, X0=None, aff=None, saff=None, mesh=None,
              mesh_spec=None, callback=None, telemetry=None):
    """Single-device dense backend: full affinities, any registered
    strategy, the whole iteration fused into one jitted XLA program
    (`core/minimize.DenseObjective`)."""
    _reject_saff(saff, "dense")
    with _tracing(telemetry):
        aff, X0 = _dense_problem(spec, Y, X0, aff)
        strategy = strategy_entry(spec.strategy).dense_factory(
            spec, **dict(spec.strategy_opts))
        ls = spec.resolved_ls()
        lam = jnp.asarray(spec.lam, dtype=X0.dtype)
        obj = DenseObjective(aff, spec.kind, lam, strategy, ls, X0,
                             impl=tuple(sorted(spec.kernel_args().items())))
        return fit_loop(obj, X0, make_loop_config(spec, ls), callback,
                        telemetry=telemetry)


def fit_dense_mesh(spec, Y, *, X0=None, aff=None, saff=None, mesh=None,
                   mesh_spec=None, callback=None, telemetry=None):
    if aff is not None:
        raise ValueError("precomputed aff= is dense-backend-only (the mesh "
                         "backend shards its own affinities)")
    _reject_saff(saff, "dense-mesh")
    with _tracing(telemetry):
        obj, X = build_dense_mesh_objective(spec, mesh, mesh_spec, Y, X0,
                                            strategy=spec.strategy)
        return fit_loop(obj, X, make_loop_config(spec, spec.resolved_ls()),
                        callback, telemetry=telemetry)


def _fit_sparse(spec, Y, X0, saff, mesh, mesh_spec, callback, telemetry,
                sharded):
    with _tracing(telemetry):
        obj, X = build_sparse_objective(spec, mesh, mesh_spec, Y, X0,
                                        strategy=spec.strategy,
                                        sharded=sharded, saff=saff)
        return fit_loop(obj, X, make_loop_config(spec, spec.resolved_ls()),
                        callback, telemetry=telemetry)


def fit_sparse(spec, Y, *, X0=None, aff=None, saff=None, mesh=None,
               mesh_spec=None, callback=None, telemetry=None):
    if aff is not None:
        raise ValueError("precomputed aff= is dense-backend-only (the "
                         "sparse backend builds its own ELL graph; pass "
                         "saff= for a precomputed one)")
    return _fit_sparse(spec, Y, X0, saff, mesh, mesh_spec, callback,
                       telemetry, sharded=False)


def fit_sparse_sharded(spec, Y, *, X0=None, aff=None, saff=None, mesh=None,
                       mesh_spec=None, callback=None, telemetry=None):
    if aff is not None:
        raise ValueError("precomputed aff= is dense-backend-only (the "
                         "sparse backend builds its own ELL graph; pass "
                         "saff= for a precomputed one)")
    if saff is not None:
        raise ValueError(
            "precomputed saff= is not supported on the sparse-sharded "
            "backend yet (the shards are cut from the build); use the "
            "sparse or tree backend")
    return _fit_sparse(spec, Y, X0, None, mesh, mesh_spec, callback,
                       telemetry, sharded=True)


def fit_tree(spec, Y, *, X0=None, aff=None, saff=None, mesh=None,
             mesh_spec=None, callback=None, telemetry=None):
    """Deterministic Barnes-Hut backend: exact ELL attractive terms plus
    grid far-field repulsion (sparse/farfield.py), O(N log N), 2-D only,
    bit-identical across repeated runs."""
    if aff is not None:
        raise ValueError("precomputed aff= is dense-backend-only (the "
                         "tree backend builds its own ELL graph; pass "
                         "saff= for a precomputed one)")
    with _tracing(telemetry):
        obj, X = build_tree_objective(spec, Y, X0, strategy=spec.strategy,
                                      saff=saff)
        return fit_loop(obj, X, make_loop_config(spec, spec.resolved_ls()),
                        callback, telemetry=telemetry)


attach_backend_impl("dense", fit_dense)
attach_backend_impl("dense-mesh", fit_dense_mesh)
attach_backend_impl("sparse", fit_sparse)
attach_backend_impl("sparse-sharded", fit_sparse_sharded)
attach_backend_impl("tree", fit_tree)
