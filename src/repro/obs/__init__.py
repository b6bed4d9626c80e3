"""`repro.obs`: structured run telemetry, solver diagnostics and trace
export for the fit engine, backends and kernels (docs/observability.md).

The paper's central claim is a cost/benefit one — the spectral direction
"adds nearly no overhead to the gradient" — so the repo needs to observe
more than energy and wall-clock.  This package is the substrate:

  * `RunRecorder` — typed per-iteration records (energy, |grad|, accepted
    step, line-search evals, PCG iterations/residual, streaming-Z EMA,
    device memory) to an in-memory buffer and optional JSONL file, plus
    named phase timings (graph-build / setup / compile / solve);
  * `SpanTracer` + `span()` — a contextvar-scoped span-timer API with
    Chrome-trace-event (Perfetto-loadable) export and an optional
    `jax.profiler.TraceAnnotation` hookup; instrumentation points in
    `embed/engine.py`, `api/backends.py`, `sparse/graph.py` and
    `sparse/sharding.py` are no-ops (one contextvar read) unless a tracer
    is active, so the hot paths stay provably cheap when telemetry is off
    (device work is attributed by `jax.named_scope` in the traced code,
    docs/observability.md);
  * `Telemetry` — the user-facing switch: `Embedding.fit(telemetry=...)`
    accepts `True`, an output directory, or a `Telemetry` instance;
  * `python -m repro.obs.report run.jsonl [other.jsonl]` renders one run
    or diffs two.

Nothing here imports the engine, backends or kernels — only the reverse —
so every layer of the stack can depend on `repro.obs` without cycles.
"""
from .record import (IterationRecord, RequestRecord, RunRecorder,
                     device_memory_stats, load_jsonl, load_requests)
from .spans import (SpanTracer, activate, block_if_traced,
                    current_tracer, span)
from .telemetry import Telemetry, resolve_telemetry

__all__ = [
    "IterationRecord",
    "RequestRecord",
    "RunRecorder",
    "SpanTracer",
    "Telemetry",
    "activate",
    "block_if_traced",
    "current_tracer",
    "device_memory_stats",
    "load_jsonl",
    "load_requests",
    "resolve_telemetry",
    "span",
]
