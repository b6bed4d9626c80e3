"""repro.obs: JSONL schema round-trip, Chrome-trace validity, solver
diagnostics surfacing, callback compat, memory-stat guards, and resume
contiguity of the telemetry stream across a checkpoint boundary."""
from __future__ import annotations

import json
import warnings

import numpy as np
import pytest

from repro.api import Embedding, EmbedSpec
from repro.obs import (IterationRecord, RunRecorder, SpanTracer, Telemetry,
                       activate, current_tracer, device_memory_stats,
                       load_jsonl, resolve_telemetry, span)
from repro.obs.report import main as report_main

from tests.conftest import three_loops


def _sparse_spec(tmp_path=None, kind="ee", iters=6, **kw):
    return EmbedSpec(kind=kind, lam=50.0 if kind == "ee" else 1.0,
                     strategy="sd", backend="sparse", perplexity=4.0,
                     n_neighbors=8, max_iters=iters, tol=0.0, **kw)


@pytest.fixture(scope="module")
def Y():
    return three_loops(n_per=40, loops=3, dim=10)


# -- record / JSONL schema -------------------------------------------------------


def test_jsonl_schema_round_trip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = RunRecorder(jsonl_path=path)
    rec.set_meta(backend="sparse", n=120)
    rec.record_phase("graph-build", 0.25)
    r0 = IterationRecord(it=1, energy=3.5, grad_norm=0.5, alpha=0.1,
                         n_evals=2, t=0.01, iter_s=0.01,
                         extras={"pcg_iters": 7.0, "pcg_residual": 1e-4})
    rec.record(r0)
    rec.record(IterationRecord(it=2, energy=3.0, grad_norm=0.4, alpha=0.2,
                               n_evals=1, t=0.02, iter_s=0.01))
    rec.flush()

    meta, phases, records = load_jsonl(path)
    assert meta == {"backend": "sparse", "n": 120}
    assert phases == [{"name": "graph-build", "dur_s": 0.25}]
    assert records[0] == r0
    assert records[1].extras == {}

    # append-only schema: unknown record types and keys must be ignored
    with open(path, "a") as f:
        f.write(json.dumps({"type": "espresso", "shots": 2}) + "\n")
        f.write(json.dumps({**r0.to_json(), "it": 3,
                            "a_future_key": "x"}) + "\n")
    _, _, records = load_jsonl(path)
    assert [r.it for r in records] == [1, 2, 3]

    s = rec.summary()
    assert s["n_iters"] == 2 and s["total_evals"] == 3
    assert s["mean_pcg_iters"] == pytest.approx(7.0)


def test_device_memory_stats_guards():
    class NoneDev:
        def memory_stats(self):
            return None

    class RaisingDev:
        def memory_stats(self):
            raise RuntimeError("driver says no")

    class FullDev:
        def memory_stats(self):
            return {"bytes_in_use": 123, "peak_bytes_in_use": 456,
                    "largest_alloc": 9}

    assert device_memory_stats(NoneDev()) == {}
    assert device_memory_stats(RaisingDev()) == {}
    assert device_memory_stats(object()) == {}          # no method at all
    assert device_memory_stats(FullDev()) == {
        "mem_bytes_in_use": 123.0, "mem_peak_bytes": 456.0}
    # the real default device, whatever the backend, must never raise
    assert isinstance(device_memory_stats(), dict)


# -- spans / tracer --------------------------------------------------------------


def test_span_is_noop_without_tracer():
    assert current_tracer() is None
    with span("anything", phase=True, n=3) as s:
        assert s is None                                # shared no-op


def test_tracer_collects_and_scopes():
    tr = SpanTracer()
    with activate(tr):
        assert current_tracer() is tr
        with span("outer", n=1):
            with span("inner"):
                pass
        with activate(tr):                              # reentrant
            with span("again"):
                pass
    assert current_tracer() is None
    names = [e["name"] for e in tr.to_chrome_trace()["traceEvents"]]
    assert set(names) == {"outer", "inner", "again"}
    ev = {e["name"]: e for e in tr.events}
    assert ev["outer"]["args"] == {"n": 1}
    # inner nested within outer on the host timeline
    assert ev["inner"]["ts"] >= ev["outer"]["ts"]
    assert ev["inner"]["dur"] <= ev["outer"]["dur"]


def test_phase_span_mirrors_into_recorder():
    rec = RunRecorder()
    tr = SpanTracer(recorder=rec)
    with activate(tr):
        with span("graph-build", phase=True):
            pass
        with span("not-a-phase"):
            pass
    assert [p["name"] for p in rec.phases] == ["graph-build"]


def test_resolve_telemetry_contract(tmp_path):
    assert resolve_telemetry(None) is None
    assert resolve_telemetry(False) is None
    t = resolve_telemetry(True)
    assert isinstance(t, Telemetry) and t.jsonl is None and t.trace is None
    d = tmp_path / "runs"
    t = resolve_telemetry(str(d))
    assert d.is_dir()
    assert t.jsonl == str(d / "run.jsonl") and t.trace == str(d / "trace.json")
    t2 = Telemetry()
    assert resolve_telemetry(t2) is t2
    with pytest.raises(TypeError):
        resolve_telemetry(3.14)


# -- end-to-end: fit with telemetry ----------------------------------------------


def test_sparse_fit_telemetry_end_to_end(tmp_path, Y):
    out = tmp_path / "tel"
    emb = Embedding(_sparse_spec()).fit(Y, telemetry=str(out))
    res = emb.result_

    # diagnostics table on the result: PCG work actually surfaced
    assert res.diagnostics is not None
    assert len(res.diagnostics) == res.n_iters
    for d in res.diagnostics:
        assert d["pcg_iters"] >= 1
        assert 0.0 <= d["pcg_residual"]
        assert d["iter_s"] > 0 and d["n_evals"] >= 1
    assert [d["it"] for d in res.diagnostics] == \
        list(range(1, res.n_iters + 1))

    # JSONL mirrors the same iterations
    meta, phases, records = load_jsonl(str(out / "run.jsonl"))
    assert meta["backend"] == "sparse" and meta["strategy"] == "sd"
    assert [r.it for r in records] == [d["it"] for d in res.diagnostics]
    assert {p["name"] for p in phases} >= {"graph-build", "setup", "compile"}

    # the acceptance trace: valid Chrome trace-event JSON with spans for
    # graph build, compile, and at least one solve iteration
    trace = json.loads((out / "trace.json").read_text())
    events = trace["traceEvents"]
    names = [e["name"] for e in events]
    assert {"graph-build", "compile"} <= set(names)
    assert sum(n == "solve-iter" for n in names) >= 1
    for e in events:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert "pid" in e and "tid" in e

    assert emb.telemetry_.summary()["mean_pcg_iters"] >= 1


def test_normalized_model_surfaces_z_ema(Y):
    emb = Embedding(_sparse_spec(kind="tsne", iters=4)).fit(Y,
                                                            telemetry=True)
    d = emb.result_.diagnostics[-1]
    assert d["z_ema"] > 0
    assert d["pcg_iters"] >= 1


def test_no_telemetry_means_no_diagnostics(Y):
    emb = Embedding(_sparse_spec(iters=3)).fit(Y)
    assert emb.result_.diagnostics is None
    assert emb.telemetry_ is None


# -- engine callback forms -------------------------------------------------------


def test_three_arg_callback_sees_every_iteration_without_diagnostics(Y):
    """The 3-arg form is the no-diagnostics contract: it sees every
    iteration, raises no warning, and the engine collects no diagnostics
    for it (so no per-iteration device->host read is added)."""
    seen = []

    def plain(it, X, e):
        seen.append((it, float(e)))

    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        emb = Embedding(_sparse_spec(iters=3)).fit(Y, callback=plain)
    assert [it for it, _ in seen] == [1, 2, 3]
    assert emb.result_.diagnostics is None


def test_three_arg_callback_never_calls_objective_diagnostics(Y):
    """The engine asks the objective for diagnostics only when someone
    listens for them; a 3-arg callback does not."""
    from repro.embed.engine import fit_loop
    from repro.embed.trainer import build_sparse_objective, make_loop_config

    spec = _sparse_spec(iters=3)
    obj, X0 = build_sparse_objective(spec, Y=Y)

    def no_diagnostics():
        raise AssertionError("diagnostics() called for a 3-arg callback")

    obj.diagnostics = no_diagnostics
    seen = []
    res = fit_loop(obj, X0, make_loop_config(spec, spec.resolved_ls()),
                   lambda it, X, e: seen.append(it))
    assert seen == [1, 2, 3]
    assert res.diagnostics is None


def test_four_arg_callback_gets_diagnostics(Y):
    diags = []
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        Embedding(_sparse_spec(iters=3)).fit(
            Y, callback=lambda it, X, e, diag: diags.append(diag))
    assert len(diags) == 3
    for d in diags:
        assert d["pcg_iters"] >= 1 and d["it"] >= 1 and "energy" in d


def test_on_iteration_hook(Y):
    hits = []
    from repro.embed.engine import fit_loop
    from repro.embed.trainer import build_sparse_objective, make_loop_config

    spec = _sparse_spec(iters=3)
    obj, X0 = build_sparse_objective(spec, None, None, Y, None,
                                     strategy="sd", sharded=False)
    res = fit_loop(obj, X0, make_loop_config(spec, spec.resolved_ls()),
                   on_iteration=lambda it, X, diag: hits.append((it, diag)))
    assert [it for it, _ in hits] == [1, 2, 3]
    assert all(d["pcg_iters"] >= 1 for _, d in hits)
    assert res.diagnostics is not None                  # hook implies diag


@pytest.mark.parametrize("backend", ["dense", "sparse", "tree"])
def test_telemetry_off_trajectory_unchanged(Y, backend):
    # traced with the profiler annotations on: the engine's spans and the
    # programs' device scopes change nothing the fit computes
    spec = _sparse_spec(iters=4).replace(backend=backend)
    off = Embedding(spec).fit(Y).result_
    on = Embedding(spec).fit(
        Y, telemetry=Telemetry(jax_annotations=True)).result_
    np.testing.assert_array_equal(np.asarray(off.energies),
                                  np.asarray(on.energies))
    np.testing.assert_array_equal(np.asarray(off.X), np.asarray(on.X))


# -- resume contiguity -----------------------------------------------------------


def test_resume_appends_contiguous_records(tmp_path, Y):
    tel_dir = str(tmp_path / "tel")
    spec = _sparse_spec(iters=12, checkpoint_dir=str(tmp_path / "ck"),
                        checkpoint_every=100)

    part = Embedding(spec.replace(max_iters=6))
    part.fit(Y, telemetry=tel_dir)
    resumed = Embedding(spec).resume(Y, telemetry=tel_dir)
    assert resumed.result_.resumed_from == 6

    _, _, records = load_jsonl(tel_dir + "/run.jsonl")
    # one contiguous iteration stream across the checkpoint boundary:
    # 1..6 from the interrupted fit, 7..12 appended by the resume
    assert [r.it for r in records] == list(range(1, 13))
    # and the resumed trace file is valid and has its own solve spans
    trace = json.loads((tmp_path / "tel" / "trace.json").read_text())
    assert any(e["name"] == "solve-iter" for e in trace["traceEvents"])


# -- report CLI ------------------------------------------------------------------


def test_report_cli_render_and_diff(tmp_path, Y, capsys):
    out_a = tmp_path / "a"
    Embedding(_sparse_spec(iters=3)).fit(Y, telemetry=str(out_a))
    run_a = str(out_a / "run.jsonl")

    assert report_main([run_a]) == 0
    text = capsys.readouterr().out
    assert "pcg_iters" in text and "graph-build" in text

    assert report_main([run_a, run_a, "--json"]) == 0
    diff = json.loads(capsys.readouterr().out)
    assert diff["a"]["mean_pcg_iters"] == diff["b"]["mean_pcg_iters"]
