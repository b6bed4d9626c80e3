"""Autotuner (kernels/autotune.py) + the ops.py dispatch layer that
consumes it: first-search-wins determinism, shape bucketing, the disk
cache round-trip via REPRO_AUTOTUNE_CACHE, hardware-legal tile clamping,
and dispatch-decision transparency (last_dispatch + telemetry meta)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops
from repro.kernels.autotune import KernelConfig
from repro.kernels.ref import ell_lap_matvec_ref
from repro.obs import RunRecorder, SpanTracer, activate

from tests.test_sparse_kernel import _rand_graph


@pytest.fixture(autouse=True)
def _fresh_cache(monkeypatch):
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _ok_runner(cfg, bucket_n):
    return lambda: jnp.zeros(())


# -- search + in-process cache --------------------------------------------------


def test_first_search_wins_and_same_key_hits_cache():
    cands = [KernelConfig(block_rows=8), KernelConfig(block_rows=16)]
    searched = []

    def runner(cfg, bucket_n):
        def thunk():
            searched.append(cfg.block_rows)
            if cfg.block_rows == 8:        # scores inf -> 16 must win
                raise RuntimeError("candidate fails")
            return jnp.zeros(())
        return thunk

    cfg1, hit1 = autotune.get_config("ell", n=100, k=4, d=2,
                                     candidates=cands, runner=runner)
    assert cfg1 == KernelConfig(block_rows=16) and not hit1
    n_runs = len(searched)
    assert n_runs > 0
    # same bucket (70 and 100 both round up to 128): cache hit, no re-run
    cfg2, hit2 = autotune.get_config("ell", n=70, k=4, d=2,
                                     candidates=cands, runner=runner)
    assert hit2 and cfg2 == cfg1 and len(searched) == n_runs


def test_all_candidates_failing_falls_back_to_first():
    """There is no fallback: when no candidate compiles the search raises
    with every candidate's error, and nothing is cached."""
    cands = [KernelConfig(block_rows=8), KernelConfig(block_rows=16)]

    def runner(cfg, bucket_n):
        def thunk():
            raise RuntimeError(f"nothing compiles at {cfg.block_rows}")
        return thunk

    with pytest.raises(RuntimeError, match="no candidate") as err:
        autotune.get_config("ell", n=32, k=2, d=2,
                            candidates=cands, runner=runner)
    assert "nothing compiles at 8" in str(err.value)
    assert "nothing compiles at 16" in str(err.value)
    key = autotune.cache_key("ell", n=32, k=2, d=2)
    assert [f["config"]["block_rows"] for f in autotune.failures(key)] \
        == [8, 16]
    assert key not in autotune.cached_entries()


def test_failed_candidates_listed_in_dispatch():
    """A candidate that fails is skipped, and the dispatch record names it
    with its error next to the config that won."""
    cands = [KernelConfig(block_rows=8), KernelConfig(block_rows=16)]

    def runner(cfg, bucket_n):
        def thunk():
            if cfg.block_rows == 8:
                raise ValueError("refused by the compiler")
            return jnp.zeros(())
        return thunk

    cfg, _ = autotune.get_config("ell", n=32, k=2, d=2, candidates=cands,
                                 runner=runner)
    assert cfg.block_rows == 16
    (fail,) = autotune.failures(autotune.cache_key("ell", n=32, k=2, d=2))
    assert fail["config"]["block_rows"] == 8
    assert fail["error"] == "ValueError: refused by the compiler"
    X, idx, w = _rand_graph(14, 32, 4, 2)
    ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret", lane=8)
    assert ops.last_dispatch("ell_lap_matvec")["failed"] == []


def test_shape_bucket_pow2_and_caps():
    assert autotune.shape_bucket("ell", 1, False) == 8
    assert autotune.shape_bucket("ell", 100, False) == 128
    assert autotune.shape_bucket("ell", 128, False) == 128
    assert autotune.shape_bucket("ell", 129, False) == 256
    # saturating caps keep the synthetic search inputs affordable
    assert autotune.shape_bucket("pairwise", 10**6, False) == 2048
    assert autotune.shape_bucket("pairwise", 10**6, True) == 512
    assert autotune.shape_bucket("ell", 10**6, True) == 4096


def test_cache_key_distinguishes_dtype_mode_and_k():
    base = dict(n=100, k=4, d=2)
    keys = {
        autotune.cache_key("ell", **base),
        autotune.cache_key("ell", **base, dtype="bfloat16"),
        autotune.cache_key("ell", **base, interpret=True),
        autotune.cache_key("ell", n=100, k=8, d=2),
        autotune.cache_key("pairwise", **base),
    }
    assert len(keys) == 5


# -- disk cache -----------------------------------------------------------------


def test_disk_cache_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.clear_cache()
    cands = [KernelConfig(block_rows=32)]
    cfg, hit = autotune.get_config("ell", n=64, k=4, d=2,
                                   candidates=cands, runner=_ok_runner)
    assert not hit
    payload = json.loads(path.read_text())
    assert payload["version"] == 1 and payload["entries"]
    assert KernelConfig.from_json(
        next(iter(payload["entries"].values()))) == cfg

    # simulate a fresh process: in-process cache gone, disk survives —
    # a re-search would blow up in the runner
    autotune.clear_cache()

    def boom(cfg, bucket_n):
        raise AssertionError("disk-cached key must not re-search")

    cfg2, hit2 = autotune.get_config("ell", n=64, k=4, d=2,
                                     candidates=cands, runner=boom)
    assert hit2 and cfg2 == cfg


def test_disk_cache_merge_preserves_foreign_entries(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    foreign = {"ell:n8:k1:d1:float32:other-device:compiled":
               KernelConfig(block_rows=8).to_json()}
    path.write_text(json.dumps({"version": 1, "entries": foreign}))
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.clear_cache()
    autotune.get_config("ell", n=64, k=4, d=2,
                        candidates=[KernelConfig(block_rows=16)],
                        runner=_ok_runner)
    entries = json.loads(path.read_text())["entries"]
    assert set(foreign) <= set(entries) and len(entries) == 2


# -- candidates + legal tiles ---------------------------------------------------


def test_candidates_always_include_legacy_fixed_256():
    """The legacy fixed 256 leads every candidate list at n >= 256, so it
    is the tile whenever the compiler takes it; smaller tiles follow as
    fallbacks, largest first."""
    ell = autotune.ell_candidates(n=1024, sublane=8, layouts=["vmem"])
    assert ell[0] == KernelConfig(block_rows=256, layout="vmem")
    rows = [c.block_rows for c in ell]
    assert rows == sorted(rows, reverse=True)
    pw = autotune.pairwise_candidates(n=1024, sublane=8)
    assert pw[0] == KernelConfig(block_rows=256, block_cols=256,
                                 layout="tiled")
    # the SMEM cap clips the lead tile, it does not reorder the list
    capped = autotune.ell_candidates(n=1024, sublane=8, layouts=["vmem"],
                                     max_rows=192)
    assert [c.block_rows for c in capped] == [192, 128, 64]


def test_hbm_candidates_chunk_divides_block_rows():
    for max_chunk in (None, 3, 8):
        for cfg in autotune.ell_candidates(n=4096, sublane=8,
                                           layouts=["hbm"],
                                           max_rows=120,
                                           max_chunk=max_chunk):
            assert cfg.layout == "hbm" and cfg.chunk > 0
            assert cfg.block_rows % cfg.chunk == 0
            assert cfg.chunk <= (max_chunk or 8)
    # the one divisor helper every tile shrink goes through
    assert autotune.fit_divisor(120, 8) == 8
    assert autotune.fit_divisor(120, 7) == 6
    assert autotune.fit_divisor(5000, 192, 8) == 40
    assert autotune.fit_divisor(16, 256, 8) == 16


def test_sublane_and_legal_tile():
    assert ops.sublane("float32") == 8
    assert ops.sublane("bfloat16") == 16
    # clamp to n, then round UP to the sublane multiple — never below it
    assert ops.legal_tile(256, 20, 8) == 24
    assert ops.legal_tile(16, 100, 8) == 16
    assert ops.legal_tile(20, 100, 8) == 24
    assert ops.legal_tile(256, 20, 16) == 32
    assert ops.legal_tile(1, 4, 8) == 8


# -- ops dispatch consuming the autotuner ---------------------------------------


def test_ops_autotuned_ell_deterministic_and_correct():
    X, idx, w = _rand_graph(11, 48, 4, 3)
    out1 = ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret", lane=8)
    d1 = dict(ops.last_dispatch("ell_lap_matvec"))
    out2 = ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret", lane=8)
    d2 = dict(ops.last_dispatch("ell_lap_matvec"))
    assert d1["path"] == "pallas" and d1["autotuned"]
    assert not d1["cache_hit"] and d2["cache_hit"]
    assert d2["block_rows"] == d1["block_rows"]
    r = ell_lap_matvec_ref(X, idx, w)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(r),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_dispatch_reasons_recorded():
    X, idx, w = _rand_graph(12, 32, 4, 2)
    ops.ell_lap_matvec(X, idx, w)                       # auto on CPU
    assert ops.last_dispatch("ell_lap_matvec")["reason"] == "no-tpu"
    ops.ell_lap_matvec(X, idx, w, impl="jnp")
    assert ops.last_dispatch("ell_lap_matvec")["reason"] == "forced-off"
    ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret", block_rows=16,
                       lane=8)
    disp = ops.last_dispatch("ell_lap_matvec")
    assert disp["path"] == "pallas" and disp["reason"] == "forced-on"
    assert not disp["autotuned"]                        # explicit tile


def test_dispatch_lands_in_telemetry_meta():
    X, idx, w = _rand_graph(13, 32, 4, 2)
    rec = RunRecorder()
    with activate(SpanTracer(recorder=rec)):
        ops.ell_lap_matvec(X, idx, w, impl="pallas-interpret",
                           block_rows=16, lane=8)
        ops.ell_lap_matvec(X, idx, w, impl="jnp")
    kd = rec.meta["kernel_dispatch"]["ell_lap_matvec"]
    assert kd["path"] == "jnp" and kd["reason"] == "forced-off"
