"""Each cell's check refuses its control (bench/control.py) at a size a
test run can hold: the program's bfloat16 kernel path.  The coil20 cell
runs at its own size (one data set, a short window); the mnist20k cell at
the rehearsal's.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import time

import pytest

from bench import control, harness, rehearse


@pytest.mark.parametrize("name", ["coil20-dense-ee-fit",
                                  "mnist20k-sparse-tsne-iter"])
def test_control_is_refused(name):
    import jax

    jax.clear_caches()
    cell, _ = harness.make_cell(name, seed=2 ** 31 + 13, seconds=0.5,
                                trace=False, rehearsal=True)
    if "datasets" in cell.traffic:
        cell.traffic["datasets"] = 1
    else:
        rehearse.tiny(cell)
    cell.t_start = time.perf_counter()
    checks = control.control_run(cell)
    assert any(v > lim for v, lim in checks.values()), checks
