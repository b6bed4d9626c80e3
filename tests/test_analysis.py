"""repro.analysis: lint rules against golden fixtures, baseline
add/ratchet round-trips, the repo-wide gate, and the trace-time
contract guards (compile-count pins for the dense fused step, the
sparse epoch, the sharded epoch, and warmed server buckets;
transfer/leak guards around the engine's hot step).

The compile pins encode the paper's performance contract: after warmup,
one fit iteration is ONE cached XLA program — a retrace (shape drift,
non-static python arg, rebuilt closure) fails these tests instead of
silently eating the spectral direction's speedup.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (ALL_RULES, Baseline, assert_compile_count,
                            jit_cache_size, lint_file, lint_paths,
                            load_baseline, no_implicit_transfers,
                            no_tracer_leaks, write_baseline)
from repro.analysis.lint import main as lint_main

from conftest import three_loops

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "data" / "lint"


# -- rules vs golden fixtures ----------------------------------------------------


def test_every_rule_has_a_fixture():
    covered = {p.stem.upper() for p in FIXTURES.glob("rpr*.py")}
    assert covered == set(ALL_RULES), (covered, set(ALL_RULES))


@pytest.mark.parametrize("name", sorted(ALL_RULES))
def test_golden_fixture(name):
    golden = json.loads((FIXTURES / "expected.json").read_text())
    path = FIXTURES / f"{name.lower()}.py"
    got = [{"rule": f.rule, "line": f.line, "scope": f.scope}
           for f in lint_file(path, root=REPO)]
    assert got == golden[path.name]
    # every reported rule is the fixture's own rule — no cross-rule noise
    assert {g["rule"] for g in got} == {name}


def test_fixture_dir_is_excluded_from_sweeps():
    findings = lint_paths([REPO / "tests"], root=REPO)
    assert not any(f.path.startswith("tests/data/") for f in findings)


def test_repo_is_lint_clean_against_committed_baseline():
    """The CI gate, enforced in tier-1 too: src/tests/benchmarks carry no
    findings outside analysis/baseline.json."""
    findings = lint_paths(
        [REPO / "src", REPO / "tests", REPO / "benchmarks"], root=REPO)
    baseline = load_baseline(REPO / "analysis" / "baseline.json")
    new = baseline.unmatched(findings)
    assert new == [], "\n".join(f.render() for f in new)


# -- baseline semantics ----------------------------------------------------------

VIOLATING = """\
import warnings

def old():
    warnings.warn("old", DeprecationWarning)
"""

CLEAN = """\
import warnings

def old():
    warnings.warn("old", DeprecationWarning, stacklevel=2)
"""


def _lint_tree(tmp_path):
    return lint_paths([tmp_path / "mod.py"], root=tmp_path)


def test_baseline_roundtrip_and_ratchet(tmp_path):
    mod = tmp_path / "mod.py"
    bl_path = tmp_path / "baseline.json"
    mod.write_text(VIOLATING)
    findings = _lint_tree(tmp_path)
    assert len(findings) == 1

    # a fresh baseline refuses to grow without allow_grow: the new
    # fingerprint is counted (so the gate fails) but not admitted
    added, _ = write_baseline(bl_path, findings, Baseline(entries={}),
                              allow_grow=False)
    assert added == 1 and load_baseline(bl_path).entries == {}

    # allow_grow admits it (reason TODO for review to fill in)
    added, _ = write_baseline(bl_path, findings, Baseline(entries={}),
                              allow_grow=True)
    assert added == 1
    baseline = load_baseline(bl_path)
    assert baseline.unmatched(findings) == []
    (entry,) = baseline.entries.values()
    assert entry["reason"] == "TODO" and entry["count"] == 1

    # fixing the violation ratchets the entry out on rewrite
    mod.write_text(CLEAN)
    _, removed = write_baseline(bl_path, _lint_tree(tmp_path), baseline,
                                allow_grow=False)
    assert removed == 1 and load_baseline(bl_path).entries == {}

    # reintroducing it now fails the gate again
    mod.write_text(VIOLATING)
    assert len(load_baseline(bl_path).unmatched(_lint_tree(tmp_path))) == 1


def test_baseline_count_budget(tmp_path):
    """The N+1'th identical violation in a scope is NEW even when N are
    baselined."""
    mod = tmp_path / "mod.py"
    mod.write_text(VIOLATING)
    findings = _lint_tree(tmp_path)
    bl_path = tmp_path / "baseline.json"
    write_baseline(bl_path, findings, Baseline(entries={}), allow_grow=True)
    baseline = load_baseline(bl_path)

    mod.write_text(VIOLATING.replace(
        'warnings.warn("old", DeprecationWarning)',
        'warnings.warn("old", DeprecationWarning)\n'
        '    warnings.warn("old", DeprecationWarning)'))
    doubled = _lint_tree(tmp_path)
    assert len(doubled) == 2
    assert len(baseline.unmatched(doubled)) == 1


def test_baseline_fingerprints_survive_line_drift(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(VIOLATING)
    bl_path = tmp_path / "baseline.json"
    write_baseline(bl_path, _lint_tree(tmp_path), Baseline(entries={}),
                   allow_grow=True)
    mod.write_text("# a comment pushing everything down\n\n" + VIOLATING)
    assert load_baseline(bl_path).unmatched(_lint_tree(tmp_path)) == []


def test_cli_end_to_end(tmp_path, monkeypatch, capsys):
    (tmp_path / "pkg").mkdir()
    mod = tmp_path / "pkg" / "mod.py"
    mod.write_text(VIOLATING)
    monkeypatch.chdir(tmp_path)

    assert lint_main(["pkg"]) == 1                      # no baseline yet
    assert lint_main(["pkg", "--write-baseline"]) == 1  # refuses to grow
    assert lint_main(["pkg", "--write-baseline", "--allow-grow"]) == 0
    assert lint_main(["pkg"]) == 0                      # gate green
    capsys.readouterr()
    assert lint_main(["pkg", "--no-baseline", "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out and out[0]["rule"] == "RPR006"

    mod.write_text(CLEAN)
    assert lint_main(["pkg", "--write-baseline"]) == 0  # ratchet shrink
    entries = json.loads(
        (tmp_path / "analysis" / "baseline.json").read_text())["entries"]
    assert entries == []


# -- compile-count pins ----------------------------------------------------------


@pytest.fixture(scope="module")
def data():
    return np.asarray(three_loops(n_per=24, loops=3, dim=10), np.float32)


@pytest.fixture(scope="module")
def dense_spec():
    from repro.api import EmbedSpec
    return EmbedSpec(kind="ee", lam=10.0, strategy="sd", backend="dense",
                     perplexity=8.0, n_neighbors=24, max_iters=5, tol=0.0,
                     seed=0)


@pytest.fixture(scope="module")
def fitted_dense(data, dense_spec):
    from repro.api import Embedding
    return Embedding(dense_spec).fit(data)   # warmup: traces + compiles


def test_compile_pin_dense_fused_step(data, dense_spec, fitted_dense):
    """A second fit with the same spec and shapes is pure cache hits:
    the fused `_step` is a module-level jit whose strategy/ls-config
    statics hash by value (frozen dataclasses), and the calibration
    bisection is module-jitted — ZERO XLA compiles end to end."""
    from repro.api import Embedding
    with assert_compile_count(expected=0, label="dense fused step"):
        Embedding(dense_spec).fit(data)


def test_compile_pin_sparse_epoch(data):
    from repro.api import EmbedSpec
    from repro.embed.trainer import build_sparse_objective
    spec = EmbedSpec(kind="ee", lam=50.0, perplexity=8.0, max_iters=5,
                     backend="sparse", n_neighbors=12, n_negatives=8,
                     tol=0.0)
    obj, X0 = build_sparse_objective(spec, Y=jnp.asarray(data))
    key0 = jax.random.PRNGKey(1)
    # warm the exact per-iteration sequence (incl. the eager fold_in)
    jax.block_until_ready(obj.energy_and_grad(X0, jax.random.fold_in(key0, 1)))
    with assert_compile_count(expected=0, label="sparse epoch"):
        jax.block_until_ready(
            obj.energy_and_grad(X0, jax.random.fold_in(key0, 2)))


def test_compile_pin_sharded_epoch(data):
    from repro.launch.mesh import axis_types_kwargs
    from repro.sparse import (make_sharded_energy_grad,
                              shard_sparse_affinities, sparse_affinities)
    mesh = jax.make_mesh((1, 1), ("data", "model"), **axis_types_kwargs(2))
    saff = sparse_affinities(jnp.asarray(data), k=12, perplexity=8.0,
                             model="ee")
    sg = shard_sparse_affinities(mesh, ("data",), saff)
    eg, _ = make_sharded_energy_grad(mesh, ("data",), sg, "ee",
                                     n_negatives=8)
    X = jax.random.normal(jax.random.PRNGKey(0), (data.shape[0], 2))
    key0 = jax.random.PRNGKey(1)
    jax.block_until_ready(eg(X, 50.0, jax.random.fold_in(key0, 1)))
    with assert_compile_count(expected=0, label="sharded epoch"):
        jax.block_until_ready(eg(X, 50.0, jax.random.fold_in(key0, 2)))


def test_compile_pin_server_buckets(data, fitted_dense):
    """warmup() pre-compiles every pow2 bucket — serving traffic after it
    (single rows and padded blocks alike) never compiles."""
    from repro.api import TransformSpec
    from repro.serve import EmbeddingServer
    tspec = TransformSpec(solver="rowwise", exhaustive=True, max_iters=5)
    with EmbeddingServer(fitted_dense, tspec, max_batch=4) as srv:
        srv.warmup()
        with assert_compile_count(expected=0, label="server buckets"):
            srv.transform(data[0], timeout=120.0)
            srv.transform(data[:3] + 0.01, timeout=120.0)


def test_deliberate_retrace_fails_the_guard():
    """The acceptance fixture: an intentionally-introduced retrace
    (shape drift into a warmed jit) MUST trip the pin."""
    @jax.jit
    def f(x):
        return x * 2.0

    jax.block_until_ready(f(jnp.ones((8,))))
    with pytest.raises(AssertionError, match="compile-count contract"):
        with assert_compile_count(expected=0, label="retrace fixture"):
            jax.block_until_ready(f(jnp.ones((16,))))   # new shape
    assert jit_cache_size(f) == 2


def test_compile_counter_at_most():
    @jax.jit
    def g(x):
        return x + 1.0

    x = jnp.ones((4,))   # outside: eager ones() also backend-compiles
    with assert_compile_count(at_most=1, label="first trace"):
        jax.block_until_ready(g(x))


# -- transfer / leak guards around the engine's hot step -------------------------


def _dense_objective(data):
    from repro.core import SD
    from repro.core.affinities import make_affinities
    from repro.core.linesearch import LSConfig
    from repro.core.minimize import DenseObjective
    aff = make_affinities(jnp.asarray(data), perplexity=8.0, model="ee")
    X0 = jax.random.normal(jax.random.PRNGKey(0), (data.shape[0], 2))
    return DenseObjective(aff=aff, kind="ee", lam=jnp.asarray(10.0),
                          strategy=SD(), ls_cfg=LSConfig(),
                          X0=X0), X0


def test_engine_hot_step_makes_no_implicit_transfers(data):
    """One warmed fused-step iteration — the engine's per-iteration hot
    path — runs with transfer_guard('disallow'): every array it touches
    is already on device, and the scalar extraction goes through ONE
    explicit jax.device_get."""
    obj, X0 = _dense_objective(data)
    step = obj.make_fused_step()
    solve, state = obj.make_direction_solver()
    E, G = obj.energy_and_grad(X0, None)
    alpha = jnp.ones((), X0.dtype)
    out = jax.block_until_ready(step(X0, E, G, state, alpha))  # warm
    with no_implicit_transfers():
        X, E2, G2, state2, alpha2, ne, scalars = step(*out[:4], out[4])
        # the sanctioned extraction: one explicit transfer of the packed
        # [E, |G|, alpha, n_evals], then host math
        e_host, g_host, a_host, n_ev = jax.device_get(scalars).tolist()
    assert np.isfinite(e_host) and g_host > 0.0 and a_host > 0.0
    assert n_ev == int(ne) >= 2


def test_transfer_guard_catches_implicit_h2d():
    @jax.jit
    def h(x):
        return x * 3.0

    jax.block_until_ready(h(jnp.ones((4,))))
    with pytest.raises(Exception, match="[Dd]isallow"):
        with no_implicit_transfers():
            h(np.ones((4,), np.float32))   # numpy arg: implicit upload


def test_engine_hot_step_leaks_no_tracers(data):
    obj, X0 = _dense_objective(data)
    step = obj.make_fused_step()
    _, state = obj.make_direction_solver()
    E, G = obj.energy_and_grad(X0, None)
    alpha = jnp.ones((), X0.dtype)
    jax.block_until_ready(step(X0, E, G, state, alpha))
    with no_tracer_leaks():
        jax.block_until_ready(step(X0, E, G, state, alpha))


def test_leak_guard_catches_escaped_tracer():
    escaped = []

    def leaky(x):
        escaped.append(x)
        return x * 1.0

    with pytest.raises(Exception, match="[Ll]eak"):
        with no_tracer_leaks():
            jax.jit(leaky)(jnp.ones((4,)))
