"""`EmbeddingServer`: embedding-as-a-service over a fitted `Embedding`.

The production story for `transform()` (ROADMAP north star): load a
versioned artifact once, then answer transform requests forever without a
refit.  Three mechanisms make the request path cheap and correct:

  * **micro-batching** — requests from any number of client threads ride
    a `MicroBatcher`; a batch closes at `max_batch` rows or after
    `max_delay_s`, so single-row requests still amortize the device
    dispatch;
  * **bucketed batches** — a batch of n rows is padded to the next
    power-of-two bucket (clamped to the max-batch bucket).  Keys mirror
    `kernels/autotune.py` (`transform:<kind>:n<bucket>:k..:m..:<dtype>:
    <device>`), and `cache_info()` reports hits/misses per key;
  * **the rowwise solver** — the server forces
    `TransformSpec(solver='rowwise')` semantics by default: every row's
    trajectory is independent of batch composition AND of the padding
    rows, and every batch is solved in the same fixed row-chunk program
    shape (`api/transform.py`), so micro-batching and bucketing cannot
    change any response on any device (tests/test_serve.py pins server
    == direct transform, `chip_smoke.py` pins it on a TPU).

Per-request deadlines (`timeout_s`) are enforced while queued; graceful
shutdown (`close()` / context manager) drains the queue.  With
`telemetry=` every request appends a `RequestRecord` to the recorder
(queue wait, batch compute share, end-to-end latency) and each batch runs
under a ``serve/batch`` span — the request-level counterpart of the fit
loop's iteration records (docs/observability.md).
"""
from __future__ import annotations

import time

import numpy as np

from repro.api.spec import TransformSpec
from repro.api.transform import (_resolve_k, resolve_transform_spec,
                                 transform_points)
from repro.kernels.autotune import device_kind
from repro.obs import RequestRecord, activate, resolve_telemetry, span

from .batching import MicroBatcher
from .metrics import LatencyStats


def batch_bucket(n: int, max_batch: int) -> int:
    """Next power of two >= n, clamped to the max-batch bucket — the same
    saturating pow2 bucketing as `kernels.autotune.shape_bucket`."""
    cap = 1 << max(0, int(max_batch - 1).bit_length())
    return min(cap, max(1, 1 << max(0, int(n - 1).bit_length())))


class EmbeddingServer:
    """Batched transform server over one fitted (or loaded) `Embedding`.

    `submit(y)` enqueues a single query (one (D,) row or an (r, D) block)
    and returns a Future; `transform(y)` is the blocking convenience.
    The server never mutates the estimator — `embedding_` stays
    bit-identical no matter how many requests are served.
    """

    def __init__(self, embedding, spec: TransformSpec | None = None, *,
                 max_batch: int = 64, max_delay_s: float = 0.002,
                 timeout_s: float | None = None, telemetry=None):
        if getattr(embedding, "embedding_", None) is None:
            raise ValueError(
                "EmbeddingServer needs a fitted estimator (fit() or "
                "Embedding.load() first)")
        if getattr(embedding, "_Y_train", None) is None:
            raise ValueError(
                "EmbeddingServer needs the training Y on the estimator "
                "(snapshot artifact, or pass Y_train= to Embedding.load)")
        if spec is None:
            spec = TransformSpec(solver="rowwise")
        elif spec.solver != "rowwise":
            raise ValueError(
                "EmbeddingServer requires TransformSpec(solver='rowwise') "
                "— the engine solver couples rows through its global line "
                "search, so micro-batching would change responses")
        self.embedding = embedding
        self.spec = resolve_transform_spec(embedding.spec, spec)
        self.max_batch = max_batch
        self.timeout_s = timeout_s
        self.latency = LatencyStats()
        self._tel = resolve_telemetry(telemetry)
        self._dim = int(np.asarray(embedding._Y_train).shape[1])
        self._rid = 0
        self._cache: dict[str, dict[str, int]] = {}
        self._batcher = MicroBatcher(
            self._process, max_batch=max_batch, max_delay_s=max_delay_s,
            name="embedding-serve")
        if self._tel is not None:
            self._tel.recorder.set_meta(
                serve=True, kind=embedding.spec.kind,
                n_train=int(np.asarray(embedding.embedding_).shape[0]),
                max_batch=max_batch)

    @classmethod
    def from_artifact(cls, path: str, spec: TransformSpec | None = None,
                      *, Y_train=None, **kw) -> "EmbeddingServer":
        """Serve straight from a saved artifact (`Embedding.save`)."""
        from repro.api import Embedding
        return cls(Embedding.load(path, Y_train=Y_train), spec, **kw)

    # -- request path --------------------------------------------------------
    def submit(self, y, *, timeout: float | None = None):
        """Enqueue one query — a (D,) row or an (r, D) block — and return
        a Future resolving to the (r, dim) embedding ((dim,) for a single
        row).  `timeout` defaults to the server's `timeout_s`."""
        y = np.asarray(y, dtype=np.float32)
        single = y.ndim == 1
        rows = y[None, :] if single else y
        if rows.ndim != 2 or rows.shape[1] != self._dim:
            raise ValueError(
                f"query must be ({self._dim},) or (r, {self._dim}), got "
                f"shape {y.shape}")
        t_submit = time.perf_counter()
        rid = self._rid = self._rid + 1
        fut = self._batcher.submit(
            (rid, rows, t_submit, single),
            timeout=self.timeout_s if timeout is None else timeout)
        fut.add_done_callback(
            lambda f: self._finish(f, rid, rows.shape[0], t_submit))
        return fut

    def transform(self, y, *, timeout: float | None = None):
        """Blocking submit: the embedding for `y`, or raises the request's
        failure (TimeoutError past the deadline)."""
        return self.submit(y, timeout=timeout).result()

    def _finish(self, fut, rid: int, n_rows: int, t_submit: float) -> None:
        total = time.perf_counter() - t_submit
        err = None if fut.cancelled() else fut.exception()
        status = ("ok" if err is None
                  else "timeout" if isinstance(err, TimeoutError)
                  else "error")
        if status == "ok":
            self.latency.add(total)
        if self._tel is not None:
            self._tel.recorder.record_request(RequestRecord(
                rid=rid, n_rows=n_rows,
                batch=self._batcher.stats.n_batches - 1,
                queue_s=max(0.0, total - self._last_compute_s)
                if status == "ok" else total,
                compute_s=self._last_compute_s if status == "ok" else 0.0,
                total_s=total, status=status))

    # -- batch side ----------------------------------------------------------
    _last_compute_s = 0.0

    def _cache_key(self, bucket: int, k: int, m) -> str:
        e = self.embedding.spec
        mm = "exh" if m is None else str(m)
        return (f"transform:{e.kind}:n{bucket}:k{k}:m{mm}:"
                f"float32:{device_kind()}")

    def _process(self, payloads):
        rows = [p[1] for p in payloads]
        n = sum(r.shape[0] for r in rows)
        bucket = batch_bucket(n, self.max_batch)
        Y = np.concatenate(rows, axis=0)
        if bucket > n:
            # pad with copies of the first row: the rowwise solver makes
            # padded rows invisible to real ones (batch invariance), they
            # are sliced off before the split below
            Y = np.concatenate(
                [Y, np.repeat(Y[:1], bucket - n, axis=0)], axis=0)
        est = self.embedding
        tspec = self.spec
        k = _resolve_k(est.spec, tspec, np.asarray(est._Y_train).shape[0],
                       est.spec.perplexity)
        key = self._cache_key(
            bucket, k, None if tspec.exhaustive else tspec.n_negatives)
        entry = self._cache.setdefault(key, {"hits": 0, "misses": 0})
        entry["hits" if entry["hits"] + entry["misses"] else "misses"] += 1

        t0 = time.perf_counter()
        # the worker thread starts with a fresh contextvar scope, so the
        # server's tracer (if any) must be re-activated here
        with activate(self._tel.tracer if self._tel else None):
            with span("serve/batch", phase=False, n=n, bucket=bucket,
                      requests=len(payloads)):
                X, _ = transform_points(
                    est.spec, est._Y_train, est.embedding_, Y, tspec=tspec)
        self._last_compute_s = time.perf_counter() - t0
        X = np.asarray(X)[:n]

        out, off = [], 0
        for rid, r, t_submit, single in payloads:
            x = X[off:off + r.shape[0]]
            out.append(x[0] if single else x)
            off += r.shape[0]
        return out

    # -- lifecycle / introspection -------------------------------------------
    def warmup(self, batch_sizes=None) -> list[str]:
        """Pre-compile the bucketed transform steps for the given batch
        sizes (default: every pow2 bucket up to max_batch, i.e. the full
        set live traffic can hit) so first requests don't pay compilation;
        returns the cache keys touched."""
        if batch_sizes is None:
            batch_sizes = [1 << i
                           for i in range((self.max_batch - 1)
                                          .bit_length() + 1)]
        anchor = np.asarray(self.embedding._Y_train)
        keys = []
        for b in batch_sizes:
            b = max(1, min(int(b), self.max_batch))
            y = np.repeat(anchor[:1], b, axis=0)
            self._process([(0, y.astype(np.float32), time.perf_counter(),
                            False)])
            keys.append(self._cache_key(
                batch_bucket(b, self.max_batch),
                _resolve_k(self.embedding.spec, self.spec, anchor.shape[0],
                           self.embedding.spec.perplexity),
                None if self.spec.exhaustive else self.spec.n_negatives))
        return keys

    def cache_info(self) -> dict:
        """Per-bucket pre-jitted-step cache counters, autotune-style
        keys."""
        return {k: dict(v) for k, v in self._cache.items()}

    def stats(self) -> dict:
        """Serving counters + latency percentiles (milliseconds)."""
        s = self._batcher.stats
        out = {"latency": self.latency.snapshot(),
               "cache": self.cache_info(), **s.as_dict()}
        if s.n_batches:
            out["mean_batch"] = s.n_rows / s.n_batches
        return out

    def close(self, *, drain: bool = True) -> None:
        self._batcher.close(drain=drain)
        if self._tel is not None:
            self._tel.finalize()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
