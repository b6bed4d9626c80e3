"""Work counts and least times on known shapes."""
import pytest

from bench import peaks
from bench.harness import BENCH, load_module


def work(op):
    return load_module(f"{BENCH}/work/{op}.py").work


def test_ell_lap_matvec_counts_index_weight_and_rows():
    flops, nbytes = work("ell_lap_matvec")(n=20000, k=150, d=2)
    assert flops == 2 * 20000 * 150 * 2 + 2 * 20000 * 2
    assert nbytes == 20000 * 150 * 8 + 2 * 20000 * 2 * 4


def test_pairwise_terms_counts_two_weight_matrices():
    flops, nbytes = work("pairwise_terms")(n=720, d=2)
    assert flops == 720 * 720 * (7 * 2 + 6)
    assert nbytes == 2 * 720 * 720 * 4 + 3 * 720 * 2 * 4


def test_least_time_takes_the_binding_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = peaks.least_seconds(1e9, 819e9, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(1.0)
    t, bound = peaks.least_seconds(197e12, 1.0, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(1.0)
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_mnist20k_forward_matvec_least_time():
    # 24.3 MB at 819 GB/s: about 30 microseconds
    flops, nbytes = work("ell_lap_matvec")(n=20000, k=150, d=2)
    t, bound = peaks.least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(24.32e6 / 819e9, rel=1e-3)
