"""The per-layer readers of program spans and device scopes on hand-made
events (bench/program_trace.py).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import types

import pytest

from bench import program_trace as pt
from bench import trace as tr
from bench.harness import BENCH, ReadContext, load_module
from bench.metrics_common import idle_share
from bench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def host(name, a, b):
    return Event(HOST, "python", name, a, b - a)


def op(name, a, b, scope=""):
    return Event(DEV, tr.OPS_LINE, name, a, b - a, scope)


def reader(metric):
    return load_module(os.path.join(BENCH, "metrics", metric + ".py")).read


def ctx_of(events, **counters):
    cell = types.SimpleNamespace(note=lambda msg: None)
    ctx = ReadContext(cell, counters, tr.reduce(events), "TPU v5 lite")
    pt.use(ctx, events)
    return ctx


def fit_window():
    """Two fits in a 1000 ns window.  Fit 1: pre-loop 0..200 with device
    work 50..100, loop 200..450 (two iterations) with work 220..260 and
    320..360, iter-host tails; fit 2 likewise shifted by 500."""
    evs = [host(tr.WINDOW, 0, 1000)]
    for s in (0, 500):
        evs += [host("bench/fit", s, s + 480),
                host("graph-build", s + 10, s + 120),
                op("custom-call.1", s + 50, s + 100, "jit(f)/affinities/x"),
                host("solve-iter", s + 200, s + 300),
                host("step", s + 205, s + 280),
                op("fusion.2", s + 220, s + 260, "jit(_step)/objective/y"),
                host("iter-host", s + 300, s + 310),
                host("solve-iter", s + 310, s + 400),
                op("fusion.3", s + 320, s + 360, "jit(_step)/objective/y"),
                host("iter-host", s + 400, s + 450)]
    return evs


def test_preloop_idle_share_reads_fit_start_to_first_iteration():
    ctx = ctx_of(fit_window())
    # per fit: 200 ns before the loop, 50 of them busy
    assert reader("preloop_idle_share.fit")(ctx) == pytest.approx(
        100.0 * 2 * 150 / 1000)


def test_loop_idle_share_reads_first_iteration_to_last_iter_host():
    ctx = ctx_of(fit_window())
    # per fit: 250 ns of loop, 80 of them busy
    assert reader("loop_idle_share.fit")(ctx) == pytest.approx(
        100.0 * 2 * 170 / 1000)


def test_phase_shares_stay_within_the_window_idle_share():
    ctx = ctx_of(fit_window())
    pre = reader("preloop_idle_share.fit")(ctx)
    loop = reader("loop_idle_share.fit")(ctx)
    whole = idle_share(ctx)
    assert pre + loop <= whole + 1e-9
    # what neither covers: each fit's tail (450..500) and the loop's
    # host gap between fits
    assert whole - (pre + loop) == pytest.approx(100.0 * 2 * 50 / 1000)


def test_fit_shares_read_nothing_without_the_programs_spans():
    # an older program: solve-iter but no iter-host, no scopes
    evs = [e for e in fit_window() if e.name != "iter-host"]
    ctx = ctx_of(evs)
    assert reader("loop_idle_share.fit")(ctx) is None
    assert reader("preloop_idle_share.fit")(ctx) is not None
    ctx = ctx_of([e for e in evs if e.name != "solve-iter"])
    assert reader("preloop_idle_share.fit")(ctx) is None


def module(name, a, b):
    return Event(DEV, pt.MODULES_LINE, name, a, b - a)


#: the programs' HLO protos as `hlo_op_names` reads them: instruction ->
#: op_name (a TPU trace names an op by its instruction and carries no
#: metadata)
SOLVE = "jit(solve)/direction-solve/while"
OP_NAMES = {
    "jit_solve(11)": {
        "while.2": SOLVE,
        "_ell_pallas.15": SOLVE + "/body/laplacian/forward/jit(_ell_pallas)"
                          "/pallas_call",
        "_ell_pallas.16": SOLVE + "/body/laplacian/reverse/jit(_ell_pallas)"
                          "/pallas_call"},
    "jit_eg(12)": {"fusion.7": "jit(eg)/objective/mul"},
    "jit_e_only(13)": {"fusion.7": "jit(e_only)/objective/add"},
}


def iter_window():
    """Two outer iterations of a sparse fit in a 1000 ns window, as a TPU
    trace shows them: ops named by their HLO text inside the programs'
    `XLA Modules` events.  Each iteration's PCG `while` op encloses one
    forward and one reverse ELL kernel; two objective evaluations and an
    unscoped add follow.  The line search's module event carries another
    id than its proto (matched by base name)."""
    evs = [host(tr.WINDOW, 0, 1000)]
    for s in (0, 500):
        evs += [module("jit_solve(11)", s + 5, s + 315),
                op("%while.2 = (f32[20000,2]) while(...)", s + 10, s + 310),
                op("%_ell_pallas.15 = f32[20160,128] custom-call(...)",
                   s + 20, s + 80),
                op("%_ell_pallas.16 = f32[20032,128] custom-call(...)",
                   s + 100, s + 300),
                module("jit_eg(12)", s + 325, s + 375),
                op("%fusion.7 = f32[] fusion(...)", s + 330, s + 370),
                module("jit_e_only(99)", s + 378, s + 402),
                op("%fusion.7 = f32[] fusion(...)", s + 380, s + 400),
                op("%copy.1 = f32[20000,2] copy(...)", s + 420, s + 430)]
    return evs


def iter_ctx(events=None):
    cell = types.SimpleNamespace(note=lambda msg: None)
    events = events or iter_window()
    ctx = ReadContext(cell, {"iters": 2}, tr.reduce(events), "TPU v5 lite")
    pt.use(ctx, events, OP_NAMES)
    return ctx


def test_solve_ms_counts_a_while_and_its_kernels_once():
    ctx = iter_ctx()
    # the while op (300 ns) encloses both kernels (60 + 200 ns)
    assert reader("solve_ms.iter")(ctx) == pytest.approx(300e-6)


def test_objective_ms_reads_every_evaluation():
    ctx = iter_ctx()
    assert reader("objective_ms.iter")(ctx) == pytest.approx(60e-6)


def test_ell_reverse_ms_reads_the_reverse_products_only():
    ctx = iter_ctx()
    assert reader("ell_reverse_ms.iter")(ctx) == pytest.approx(200e-6)


def test_scopes_match_whole_path_components():
    sp = pt.split(iter_window(), OP_NAMES)
    assert pt.scope_s(sp, "laplacian") == pytest.approx(520e-9)
    assert pt.scope_s(sp, "laplacian/rev") == 0.0   # part of a component
    assert pt.scope_s(sp, "objectiv") == 0.0


def test_scope_readers_read_nothing_without_scopes():
    cell = types.SimpleNamespace(note=lambda msg: None)
    ctx = ReadContext(cell, {"iters": 2}, tr.reduce(iter_window()), "")
    pt.use(ctx, iter_window(), {})          # an older program's protos
    for m in ("solve_ms.iter", "objective_ms.iter", "ell_reverse_ms.iter"):
        assert reader(m)(ctx) is None


def test_op_outside_its_module_takes_no_scope():
    evs = [e._replace(start_ns=e.start_ns + 1000) if e.name.startswith(
        "%fusion") else e for e in iter_window()]
    evs[0] = host(tr.WINDOW, 0, 2000)
    assert reader("objective_ms.iter")(iter_ctx(evs)) is None


@pytest.mark.parametrize("other,objective_ms,ambiguous", [
    # jit_eg compiled twice: fusion.7 is the objective in one program
    # and unscoped in the other, so the op cannot tell which it ran:
    # only e_only's 20 ns per iteration is left
    ("jit(eg)/exp", 20e-6, 2),
    # both programs give fusion.7 the same op_name: no doubt
    ("jit(eg)/objective/mul", 60e-6, 0),
])
def test_programs_of_one_name_that_disagree_give_no_scope(
        other, objective_ms, ambiguous):
    op_names = {**OP_NAMES, "jit_eg(14)": {"fusion.7": other}}
    evs = [module("jit_eg(99)", e.start_ns, e.start_ns + e.dur_ns)
           if e.name == "jit_eg(12)" else e for e in iter_window()]
    ctx = iter_ctx(evs)
    sp = pt.use(ctx, evs, op_names)
    assert sp.ambiguous == ambiguous
    assert reader("objective_ms.iter")(ctx) == pytest.approx(objective_ms)


def test_hlo_op_names_reads_the_programs_protos(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def solve(x):
        with jax.named_scope("direction-solve"):
            return jnp.sin(x) @ x.T

    x = jnp.ones((32, 32))
    solve(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    solve(x).block_until_ready()
    jax.profiler.stop_trace()
    names = pt.hlo_op_names(str(tmp_path))
    ours = [v for k, v in names.items() if k.startswith("jit_solve(")]
    assert len(ours) == 1
    assert any("jit(solve)/direction-solve/" in op for op in ours[0].values())
    assert pt.hlo_op_names(str(tmp_path / "none")) == {}


def test_idle_by_span_labels_gaps_with_program_spans_only():
    evs = fit_window() + [host("TransferFromDevice", 280, 300)]
    sp = pt.split(evs)
    idle = dict(pt.idle_by_span(sp))
    # the gap 260..320 of fit 1: its middle (290) lies in the runtime
    # event, which is no program span, and in solve-iter (step ended)
    assert "TransferFromDevice" not in idle
    assert idle["solve-iter"] == pytest.approx(2 * 60e-9)
    assert idle["graph-build"] == pytest.approx(50e-9)   # 0..50
    assert sum(idle.values()) == pytest.approx(740e-9)


def test_load_reads_the_programs_spans_from_a_recorded_trace(tmp_path,
                                                            monkeypatch):
    import jax

    from repro.api import Embedding, EmbedSpec
    from repro.obs import Telemetry

    Y = jax.random.normal(jax.random.PRNGKey(0), (60, 6))
    spec = EmbedSpec(kind="ee", lam=50.0, strategy="sd", backend="dense",
                     perplexity=5.0, max_iters=3, tol=0.0)
    Embedding(spec).fit(Y)                       # compile outside
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench/fit"):
            Embedding(spec).fit(Y, telemetry=Telemetry(jax_annotations=True))
    jax.profiler.stop_trace()
    monkeypatch.setattr(pt, "TRACE_DIR", str(tmp_path))
    notes = []
    cell = types.SimpleNamespace(note=notes.append)
    ctx = ReadContext(cell, {}, object(), "cpu")
    sp = pt.load(ctx)
    assert pt.load(ctx) is sp                    # read once
    assert [len(pt.spans(sp, n)) for n in ("solve-iter", "step", "fetch",
                                           "iter-host")] == [3, 3, 3, 3]
    (start, loop, end), = pt.fit_phases(sp)
    assert start < loop < end
    assert len(notes) == 1 and notes[0].startswith("idle by innermost")
