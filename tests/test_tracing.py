"""Where the program names its phases: engine-loop host spans, blocking
pre-loop spans while traced, device scopes in the lowered programs, and
the span clock (docs/observability.md).

With no tracer the spans are a contextvar read and the scopes are op
metadata only, so an untraced fit computes exactly what it computed
before they existed: pinned here on the compiled programs with their
metadata stripped, and on the fit's results in
`tests/test_obs.py::test_telemetry_off_trajectory_unchanged`.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Embedding, EmbedSpec
from repro.api import backends
from repro.core import make_affinities
from repro.core.linesearch import LSConfig
from repro.core.minimize import _step
from repro.core.strategies import make_strategy
from repro.embed.trainer import build_sparse_objective
from repro.obs import SpanTracer, activate, span

from tests.conftest import three_loops

LOOP_SPANS = {"step", "fetch", "direction", "line-search", "grad",
              "solve-iter", "iter-host"}


@pytest.fixture(scope="module")
def Y():
    return three_loops(n_per=30, loops=3, dim=10)


def _dense_spec(**kw):
    return EmbedSpec(kind="ee", lam=50.0, strategy="sd", backend="dense",
                     perplexity=8.0, max_iters=6, tol=0.0, **kw)


def _sparse_spec(backend="sparse", **kw):
    return EmbedSpec(kind="ee", lam=50.0, strategy="sd", backend=backend,
                     perplexity=4.0, n_neighbors=8, max_iters=5, tol=0.0,
                     **kw)


def _loop_events(emb):
    """The engine loop's spans in the order they closed."""
    return [e for e in emb.telemetry_.tracer.events if e["name"] in LOOP_SPANS]


def _within(inner, outer):
    # ts on the epoch clock, dur from perf_counter: allow 1 ms of drift
    return (outer["ts"] - 1e3 <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e3)


def _check_iterations(events, inner, n_iters):
    per = len(inner) + 2
    assert [e["name"] for e in events] == \
        (list(inner) + ["solve-iter", "iter-host"]) * n_iters
    for i in range(n_iters):
        it = events[i * per:(i + 1) * per]
        solve, tail = it[-2], it[-1]
        assert all(_within(e, solve) for e in it[:-2])
        assert solve["args"]["it"] == tail["args"]["it"] == i + 1
        assert tail["ts"] >= solve["ts"] + solve["dur"] - 1e3


def test_dense_fit_spans_step_and_fetch_in_every_iteration(Y):
    emb = Embedding(_dense_spec()).fit(Y, telemetry=True)
    _check_iterations(_loop_events(emb), ["step", "fetch"],
                      emb.result_.n_iters)


@pytest.mark.parametrize("backend,inner", [
    # stochastic: the iteration's gradient comes first, at its own key
    ("sparse", ["grad", "direction", "line-search"]),
    # deterministic: the gradient at the accepted iterate comes last
    ("tree", ["direction", "line-search", "grad"]),
])
def test_host_path_fit_spans_direction_line_search_grad(Y, backend, inner):
    emb = Embedding(_sparse_spec(backend)).fit(Y, telemetry=True)
    _check_iterations(_loop_events(emb), inner, emb.result_.n_iters)


_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_SOURCES = re.compile(
    r"\n\n(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?(?=\n\n)",
    re.DOTALL)


_NAME = re.compile(r"%[\w.\-]+")


def _program(fn, *args, **kw):
    """The optimized program, its metadata (op names, scopes) and its
    table of source lines stripped, and its instructions renamed in the
    order they appear: a name can come from whichever caller first traced
    a cached inner function."""
    text = fn.lower(*args, **kw).compile().as_text()
    text = _METADATA.sub("", _SOURCES.sub("", text))
    ids: dict[str, str] = {}
    return _NAME.sub(lambda m: ids.setdefault(m.group(0), f"%v{len(ids)}"),
                     text)


def _dense_step_args(Y):
    aff = make_affinities(Y, 8.0, model="ee")
    X = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (Y.shape[0], 2))
    strategy = make_strategy("sd")
    lam = jnp.asarray(50.0, X.dtype)
    state = strategy.init(X, aff, "ee", lam)
    E = jnp.asarray(1.0, X.dtype)
    return (strategy, "ee", LSConfig(init_step="adaptive_grow"), X, E, X,
            state, E, aff.Wp, aff.Wm, lam)


def test_scopes_leave_the_compiled_programs_unchanged(Y, monkeypatch):
    args = _dense_step_args(Y)
    obj, X = build_sparse_objective(_sparse_spec(), Y=Y)
    with_scopes = (_program(_step, *args), _program(obj._solve, X, X),
                   _program(obj._eg, X, jax.random.PRNGKey(1)))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        obj2, _ = build_sparse_objective(_sparse_spec(), Y=Y)
        without = (_program(_step, *args), _program(obj2._solve, X, X),
                   _program(obj2._eg, X, jax.random.PRNGKey(1)))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert with_scopes == without


def test_dense_preloop_outputs_ready_at_span_exit_while_traced(
        Y, monkeypatch):
    outputs, ready = {}, {}

    def keep(name, fn):
        def wrapped(*a, **k):
            outputs[name] = fn(*a, **k)
            return outputs[name]
        return wrapped

    @contextlib.contextmanager
    def checking_span(name, **kw):
        with span(name, **kw):
            yield
            if name in outputs:
                ready[name] = all(leaf.is_ready() for leaf in
                                  jax.tree.leaves(outputs[name]))

    monkeypatch.setattr(backends, "make_affinities",
                        keep("graph-build", backends.make_affinities))
    monkeypatch.setattr(backends, "laplacian_eigenmaps",
                        keep("spectral-init", backends.laplacian_eigenmaps))
    monkeypatch.setattr(backends, "span", checking_span)
    Yb = three_loops(n_per=100, loops=4, dim=32)
    Embedding(_dense_spec().replace(max_iters=1)).fit(Yb, telemetry=True)
    assert ready == {"graph-build": True, "spectral-init": True}


def test_fused_step_lowering_carries_objective_and_direction_scopes(Y):
    text = _step.lower(*_dense_step_args(Y)).as_text(debug_info=True)
    assert "/direction-solve/" in text
    assert "/objective/" in text


def test_pcg_solve_lowering_carries_solve_and_laplacian_scopes(Y):
    obj, X = build_sparse_objective(_sparse_spec(), Y=Y)
    text = obj._solve.lower(X, X).as_text(debug_info=True)
    for scope in ("direction-solve", "laplacian/forward",
                  "laplacian/reverse"):
        assert f"/{scope}/" in text, scope


def test_span_ts_is_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData

    tracer = SpanTracer(jax_annotations=True)
    jax.profiler.start_trace(str(tmp_path))
    with activate(tracer):
        with span("clock-probe"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = ProfileData.from_file(path)
    env = data.find_plane_with_name("Task Environment")
    start = int(dict(env.stats)["profile_start_time"])
    ann = [ev for plane in data.planes for line in plane.lines
           for ev in line.events if ev.name == "clock-probe"]
    assert len(ann) == 1
    ours = tracer.events[0]
    assert ours["name"] == "clock-probe"
    # the capture stores times from its start; the span, epoch times
    assert abs((start + ann[0].start_ns) - ours["ts"] * 1e3) < 1e6
    assert ours["ts"] > 1e15                  # epoch microseconds
