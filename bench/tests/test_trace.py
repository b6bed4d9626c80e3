"""The trace reduction on hand-made events and on a recorded CPU trace.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import pytest

from bench import trace as tr
from bench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, name, a, b, line=tr.OPS_LINE):
    return Event(plane, line, name, a, b - a)


def test_busy_is_the_union_of_ops_inside_the_window():
    events = [
        ev(HOST, tr.WINDOW, 100, 1100, line="python"),
        ev(DEV, "fusion.1", 50, 200),        # clipped to 100..200
        ev(DEV, "fusion.2", 150, 300),       # overlaps the first
        ev(DEV, "custom-call.7", 600, 700),
        ev(DEV, "fusion.3", 1050, 1300),     # clipped to 1050..1100
        ev(DEV, "other-line", 300, 600, line="XLA Modules"),  # not an op
    ]
    red = tr.reduce(events)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx((200 + 100 + 50) * 1e-9)
    assert red.n_chips == 1
    assert red.op_s["fusion"] == pytest.approx((100 + 150 + 50) * 1e-9)
    assert red.op_s["custom-call"] == pytest.approx(100e-9)


def test_gaps_are_labelled_by_the_innermost_host_span():
    events = [
        ev(HOST, tr.WINDOW, 0, 1000, line="python"),
        ev(HOST, "bench/fit", 0, 1000, line="python"),
        ev(HOST, "spectral-init", 300, 700, line="python"),
        ev(DEV, "a.1", 0, 300),
        ev(DEV, "b.2", 700, 1000),
    ]
    red = tr.reduce(events)
    assert red.gaps == [("spectral-init", pytest.approx(400e-9))]
    out = tr.breakdown(red)
    assert out["idle_gaps"] == [["spectral-init", pytest.approx(400e-9)]]
    assert [k for k, _ in out["device_ops"]] == ["a", "b"]


def test_busy_is_averaged_over_chips():
    events = [ev(HOST, tr.WINDOW, 0, 100, line="python"),
              ev("/device:TPU:0", "x", 0, 100),
              ev("/device:TPU:1", "x", 0, 50)]
    red = tr.reduce(events)
    assert red.n_chips == 2
    assert red.busy_s == pytest.approx(75e-9)
    assert red.op_s["x"] == pytest.approx(150e-9)


def test_kernel_seconds_sums_matching_names():
    events = [ev(HOST, tr.WINDOW, 0, 100, line="python"),
              ev(DEV, "ell_kernel.3", 0, 10), ev(DEV, "ell_kernel.4", 20, 40),
              ev(DEV, "fusion.9", 50, 60)]
    red = tr.reduce(events)
    assert tr.kernel_seconds(red, ["ell"]) == pytest.approx(30e-9)


def test_kernel_seconds_matches_the_detail_too():
    events = [ev(HOST, tr.WINDOW, 0, 100, line="python"),
              Event(DEV, tr.OPS_LINE, "custom-call.3", 0, 10,
                    "jit(_step)/jit(_ell_pallas)/pallas_call"),
              ev(DEV, "fusion.9", 50, 60)]
    red = tr.reduce(events)
    assert tr.kernel_seconds(red, ["_ell_pallas"]) == pytest.approx(10e-9)


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([ev(DEV, "x", 0, 10)])


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench/step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load(str(tmp_path))
    names = {e.name for e in events}
    assert tr.WINDOW in names and "bench/step" in names
    lo, hi = tr.window_bounds(events)
    step = [e for e in events if e.name == "bench/step"][0]
    assert lo <= step.start_ns and step.start_ns + step.dur_ns <= hi
