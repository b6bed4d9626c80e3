"""`Embedding`: the one public estimator over every backend and strategy.

    from repro.api import Embedding, EmbedSpec

    emb = Embedding(EmbedSpec(kind="tsne", strategy="sd", lam=1.0))
    X = emb.fit_transform(Y)           # backend picked by N / device count
    X_new = emb.transform(Y_new)       # out-of-sample, never re-fits

`fit` resolves `backend="auto"` by problem size and visible device count
(`repro.api.registries.resolve_backend`), builds the backend's
`Objective`, and runs the unified engine.  After `fit`:

  * `embedding_`  — the (N, dim) training embedding
  * `result_`     — the full `EngineResult` (energies, times, fevals, …)
  * `backend_`    — the resolved backend name

`transform(Y_new)` embeds unseen points against the FROZEN training
embedding (repro/api/transform.py): kNN affinities of the new rows
against the training set, a fixed-anchor objective over only the new
coordinates, run through the same `fit_loop`.  Serving new points costs
O(n_new (k + m) d) per iteration and leaves `embedding_` bit-identical.

`resume()` continues an interrupted fit from `spec.checkpoint_dir` — the
engine's checkpoint payload carries the line-search and solver state, so
the resumed trajectory is the uninterrupted one, bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.embed.engine import EngineResult
from repro.obs import resolve_telemetry

from . import registries
from .spec import EmbedSpec, TransformSpec
from .transform import transform_points

Array = jnp.ndarray


class Embedding:
    """Estimator facade: `EmbedSpec` in, embedding out.

    `mesh`/`mesh_spec` matter only to the mesh backends (`dense-mesh`,
    `sparse-sharded`); when omitted, a (n_devices, 1) host mesh is built
    on demand.  Keyword overrides construct/derive the spec:
    `Embedding(kind="tsne", lam=1.0)` == `Embedding(EmbedSpec(kind="tsne",
    lam=1.0))`.
    """

    def __init__(self, spec: EmbedSpec | None = None, *, mesh=None,
                 mesh_spec=None, **overrides):
        if spec is None:
            spec = EmbedSpec(**overrides)
        elif overrides:
            spec = dataclasses.replace(spec, **overrides)
        self.spec = spec
        self.mesh = mesh
        self.mesh_spec = mesh_spec

    # -- fitting ------------------------------------------------------------
    def _resolve_backend(self, n: int) -> str:
        n_devices = (self.mesh.devices.size if self.mesh is not None
                     else jax.device_count())
        return registries.resolve_backend(
            self.spec.backend, n=n, n_devices=n_devices,
            strategy=self.spec.strategy)

    def _mesh_for(self, backend: str):
        """The mesh a mesh backend runs on, with Auto axes: a caller's mesh
        built by plain `jax.make_mesh` (Explicit axes) is converted."""
        if not registries.BACKENDS[backend].needs_mesh:
            return self.mesh
        from repro.launch.mesh import auto_axes, make_host_mesh
        if self.mesh is None:
            self.mesh = make_host_mesh()
        return auto_axes(self.mesh)

    def fit(self, Y: Array | None, X0: Array | None = None,
            aff=None,
            callback: Callable[..., None] | None = None,
            *, saff=None, telemetry=None) -> "Embedding":
        """Fit the embedding.  `Y` is the (N, D) data; the dense backend
        alternatively accepts precomputed `aff=` (core.Affinities) so
        benchmark drivers can share one calibration across strategies, and
        the sparse/tree backends accept `saff=` (sparse.SparseAffinities)
        — the ELL analogue — so strategy sweeps share one k-NN build.

        `telemetry` switches on run observability (`repro.obs`): pass
        `True` for in-memory recording, a directory path to also write
        `run.jsonl` + `trace.json` there, or a `repro.obs.Telemetry` for
        full control.  After the fit, `self.telemetry_` holds the
        finalized object (`.summary()`, `.recorder.records`, …) and
        `result_.diagnostics` the per-iteration dict table."""
        if aff is not None and saff is not None:
            raise ValueError("pass aff= (dense) or saff= (sparse), not "
                             "both — they pin different backends")
        tel = resolve_telemetry(telemetry)
        if Y is not None:
            n = Y.shape[0]
        elif aff is not None:
            n = aff.Wp.shape[0]
        else:
            n = saff.graph.n
        if aff is not None and self.spec.backend == "auto":
            # precomputed dense affinities pin the backend: only the dense
            # path can consume them, whatever N would otherwise resolve to
            backend = "dense"
        elif saff is not None and self.spec.backend == "auto":
            # the sparse analogue: a prebuilt ELL graph pins the sparse
            # path (the user may still request backend="tree" explicitly)
            backend = "sparse"
        else:
            backend = self._resolve_backend(n)
        registries.validate_strategy_backend(self.spec.strategy, backend)
        fit_fn = registries.backend_impl(backend)
        if tel is not None:
            tel.recorder.set_meta(backend=backend, kind=self.spec.kind,
                                  strategy=self.spec.strategy, n=int(n))
        try:
            res: EngineResult = fit_fn(
                self.spec, Y, X0=X0, aff=aff, saff=saff,
                mesh=self._mesh_for(backend),
                mesh_spec=self.mesh_spec, callback=callback, telemetry=tel)
        finally:
            if tel is not None:
                tel.finalize()
        self.backend_ = backend
        self.result_ = res
        self.embedding_ = res.X
        self.telemetry_ = tel
        self._Y_train = Y
        return self

    def fit_transform(self, Y: Array, X0: Array | None = None,
                      callback=None, *, telemetry=None) -> Array:
        return self.fit(Y, X0=X0, callback=callback,
                        telemetry=telemetry).embedding_

    def resume(self, Y: Array | None = None, max_iters: int | None = None,
               *, telemetry=None) -> "Embedding":
        """Continue a checkpointed fit (bit-identical to the uninterrupted
        trajectory — the engine's payload carries line-search and solver
        state).  `max_iters` extends the iteration budget.  Passing the
        same `telemetry` directory as the original fit appends to its
        `run.jsonl`, giving one contiguous iteration record across the
        checkpoint boundary."""
        if self.spec.checkpoint_dir is None:
            raise ValueError("resume() needs spec.checkpoint_dir")
        if Y is None:
            Y = getattr(self, "_Y_train", None)
            if Y is None:
                raise ValueError("resume() needs Y (no prior fit in this "
                                 "process to take it from)")
        if max_iters is not None:
            self.spec = dataclasses.replace(self.spec, max_iters=max_iters)
        return self.fit(Y, telemetry=telemetry)

    # -- serving ------------------------------------------------------------
    def transform(self, Y_new: Array,
                  spec: TransformSpec | None = None) -> Array:
        """Embed unseen points against the frozen training embedding.

        Never re-fits: the training coordinates enter as constants, so
        `embedding_` is bit-identical before and after.  Configuration is
        a frozen `TransformSpec` (`spec=`); its zero/None fields defer to
        the fitted `EmbedSpec` (docs/serving.md).  Requires the fit to
        have seen raw `Y` (not only precomputed affinities)."""
        if getattr(self, "embedding_", None) is None:
            raise ValueError("transform() requires a fitted estimator")
        if getattr(self, "_Y_train", None) is None:
            if getattr(self, "loaded_from_", None):
                raise ValueError(
                    "transform() needs the training Y: this estimator was "
                    "loaded from a train='ref' artifact whose reference "
                    "was unavailable — pass Y_train= to Embedding.load()")
            raise ValueError(
                "transform() needs the raw training Y; this estimator was "
                "fit from precomputed affinities only")
        X_new, res = transform_points(
            self.spec, self._Y_train, self.embedding_, Y_new, tspec=spec)
        self.last_transform_result_ = res
        return X_new

    # -- persistence ---------------------------------------------------------
    def save(self, path: str, *, train: str = "snapshot",
             train_ref: str | None = None) -> str:
        """Persist the fitted estimator as a versioned artifact (one
        `.npz`: embedding + training data + frozen spec + graph stats) —
        the supported way to move a fitted `Embedding` across processes;
        pickling is unsupported (`repro.api.artifact`, docs/serving.md).
        `train='ref'` stores a path + SHA-256 instead of snapshotting Y."""
        from .artifact import save_artifact
        return save_artifact(self, path, train=train, train_ref=train_ref)

    @classmethod
    def load(cls, path: str, *, Y_train=None) -> "Embedding":
        """Reload a `save()`d artifact: returns a fitted estimator whose
        `transform()` matches the saving process bit-for-bit in the
        deterministic (exhaustive) mode — no refit ever happens."""
        from .artifact import load_artifact
        return load_artifact(path, Y_train=Y_train)

    def __reduce__(self):
        raise TypeError(
            "pickling Embedding is unsupported (jitted closures and device "
            "arrays do not survive it); use est.save(path) / "
            "Embedding.load(path) — the versioned artifact format is the "
            "supported persistence surface (docs/serving.md)")

    # -- introspection ------------------------------------------------------
    def __repr__(self):
        loaded = getattr(self, "loaded_from_", None)
        fitted = getattr(self, "backend_", None)
        if loaded:
            ver = (getattr(self, "artifact_header_", {}) or {}).get(
                "schema_version")
            state = f"loaded[v{ver}:{loaded}]"
        elif fitted:
            state = f"fitted[{fitted}]"
        else:
            state = "unfitted"
        n = getattr(self, "embedding_", None)
        if n is not None:
            state += f", n_train={n.shape[0]}"
        return (f"Embedding(kind={self.spec.kind!r}, "
                f"strategy={self.spec.strategy!r}, "
                f"backend={self.spec.backend!r}, {state})")
