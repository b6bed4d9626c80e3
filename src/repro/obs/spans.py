"""Span timers with Chrome-trace-event export.

`span(name, **args)` is the one instrumentation primitive used across the
stack (engine phases, graph build, sharded-graph placement, kernel
dispatch).  It reads a contextvar: with no active `SpanTracer` it returns
a shared no-op context manager — one dict-free contextvar read, so
instrumentation points cost nothing in uninstrumented runs (the <5%
telemetry overhead budget is asserted in the bench gate).

Spans measure HOST wall-clock: dispatch-to-completion where the code
blocks inside the span, dispatch-only otherwise.  `fit_loop` blocks on
its per-iteration results, so its `solve-iter`/`step`/`fetch` spans are
true step times; `block_if_traced` makes a pre-loop span (dense
`graph-build`, `spectral-init`) block only while a tracer is active.
Device-side attribution is `jax.named_scope`, not spans: a span around a
jitted callee that fires inside another trace would time tracing.

Export is the Chrome trace-event JSON format (`{"traceEvents": [...]}`,
complete "X" events with microsecond `ts`/`dur`), loadable in Perfetto
(ui.perfetto.dev) or `chrome://tracing`.  `ts` is on the profiler's
clock (Unix-epoch microseconds, `time.time_ns`), so the exported file
lines up with a `jax.profiler` capture without an offset; `dur` comes
from `perf_counter`.  With `jax_annotations=True` every span
additionally enters a `jax.profiler.TraceAnnotation`, so the same names
show up inside a `jax.profiler.trace` capture next to the XLA events —
the hookup is best-effort and degrades to host spans when the profiler
is unavailable.
"""
from __future__ import annotations

import contextvars
import json
import time
from typing import Any

_ACTIVE: contextvars.ContextVar["SpanTracer | None"] = \
    contextvars.ContextVar("repro_obs_tracer", default=None)


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("tracer", "name", "phase", "args", "t0", "ts_ns", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, phase: bool,
                 args: dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.phase = phase
        self.args = args
        self._ann = None

    def __enter__(self):
        if self.tracer.jax_annotations:
            try:
                from jax.profiler import TraceAnnotation
                self._ann = TraceAnnotation(self.name)
                self._ann.__enter__()
            except Exception:
                self._ann = None
        self.tracer._depth += 1
        self.ts_ns = time.time_ns()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.tracer._depth -= 1
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
        self.tracer._close(self.name, self.ts_ns, t1 - self.t0, self.args,
                           self.phase)
        return False


class SpanTracer:
    """Collects spans as Chrome-trace 'X' (complete) events.

    `recorder` (a `RunRecorder`) is optional: spans entered with
    `phase=True` mirror their duration into the recorder's JSONL as a
    phase record, so the headline phase timings (graph-build, setup,
    compile) live in BOTH artifacts without double instrumentation.
    """

    def __init__(self, jax_annotations: bool = False, recorder=None):
        self.jax_annotations = jax_annotations
        self.recorder = recorder
        self.events: list[dict[str, Any]] = []
        self._depth = 0

    def span(self, name: str, *, phase: bool = False, **args: Any) -> _Span:
        return _Span(self, name, phase, args)

    def _close(self, name: str, ts_ns: int, dur_s: float,
               args: dict[str, Any], phase: bool) -> None:
        ev = {
            "name": name,
            "ph": "X",
            "ts": ts_ns / 1e3,       # epoch microseconds: the profiler's clock
            "dur": dur_s * 1e6,
            "pid": 0,
            "tid": 0,
        }
        if args:
            ev["args"] = args
        self.events.append(ev)
        if phase and self.recorder is not None:
            self.recorder.record_phase(name, dur_s)

    # -- export -------------------------------------------------------------
    def to_chrome_trace(self) -> dict[str, Any]:
        return {
            "traceEvents": sorted(self.events, key=lambda e: e["ts"]),
            "displayTimeUnit": "ms",
        }

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


def current_tracer() -> SpanTracer | None:
    return _ACTIVE.get()


class _Activation:
    """Context manager installing a tracer in the current context; nesting
    the same tracer is fine (tokens restore the previous value)."""

    __slots__ = ("tracer", "_token")

    def __init__(self, tracer: SpanTracer | None):
        self.tracer = tracer

    def __enter__(self):
        self._token = _ACTIVE.set(self.tracer)
        return self.tracer

    def __exit__(self, *exc):
        _ACTIVE.reset(self._token)
        return False


def activate(tracer: SpanTracer | None) -> _Activation:
    """`with activate(tracer): ...` scopes `span()` to this tracer.
    `activate(None)` is a supported no-op scope (backends pass their
    telemetry's tracer straight through, active or not)."""
    return _Activation(tracer)


def block_if_traced(tree):
    """`tree` after its arrays are ready while a tracer is active, so the
    span around its dispatch times the device work too; unchanged (no
    wait) when none is."""
    if _ACTIVE.get() is None:
        return tree
    import jax

    return jax.block_until_ready(tree)


def span(name: str, *, phase: bool = False, **args: Any):
    """Time a block against the ambient tracer; no-op when none is
    active.  `phase=True` additionally mirrors the duration into the
    tracer's recorder as a named phase record (JSONL)."""
    t = _ACTIVE.get()
    if t is None:
        return _NOOP
    return t.span(name, phase=phase, **args)
