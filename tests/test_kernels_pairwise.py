"""Pallas pairwise kernel vs the pure-jnp oracle: shape/dtype/kind sweep.

The kernel runs in interpret mode on CPU (the container has no TPU); the
BlockSpec tiling, padding, and accumulation logic are identical to the TPU
path, so this validates everything except Mosaic codegen.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is an optional dev dependency ([test] extra); the shim runs a
# deterministic sweep when it is missing
from tests._hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import KINDS, pairwise_terms_ref


def _rand_problem(seed: int, n: int, d: int, dtype=jnp.float32):
    kx, ka, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(kx, (n, d), dtype=dtype)
    eye = jnp.eye(n, dtype=dtype)
    Wa = jnp.abs(jax.random.normal(ka, (n, n), dtype=dtype))
    Wa = 0.5 * (Wa + Wa.T) * (1 - eye)
    Wb = jnp.abs(jax.random.normal(kb, (n, n), dtype=dtype))
    Wb = 0.5 * (Wb + Wb.T) * (1 - eye)
    return X, Wa, Wb


def _check(X, Wa, Wb, kind, br, bc, lane=8, tol=5e-5):
    r = pairwise_terms_ref(X, Wa, Wb, kind)
    p = ops.pairwise_terms(X, Wa, Wb, kind, impl="pallas", interpret=True,
                           block_rows=br, block_cols=bc, lane=lane)
    np.testing.assert_allclose(np.asarray(p.la_x), np.asarray(r.la_x),
                               rtol=tol, atol=tol * float(jnp.max(jnp.abs(r.la_x)) + 1))
    np.testing.assert_allclose(np.asarray(p.lb_x), np.asarray(r.lb_x),
                               rtol=tol, atol=tol * float(jnp.max(jnp.abs(r.lb_x)) + 1))
    np.testing.assert_allclose(float(p.e_plus), float(r.e_plus), rtol=1e-4)
    np.testing.assert_allclose(float(p.s), float(r.s), rtol=1e-4)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n,d,br,bc", [
    (64, 2, 16, 16),
    (96, 3, 32, 16),
    (130, 2, 64, 32),   # ragged N -> zero-padding path
    (33, 5, 16, 16),    # ragged both
])
def test_kernel_matches_oracle(kind, n, d, br, bc):
    X, Wa, Wb = _rand_problem(0, n, d)
    _check(X, Wa, Wb, kind, br, bc)


@pytest.mark.parametrize("kind", ["ee", "tsne"])
def test_kernel_bf16_inputs(kind):
    """bf16 inputs are upcast to f32 accumulators inside the kernel."""
    X, Wa, Wb = _rand_problem(1, 64, 2)
    Xb = X.astype(jnp.bfloat16)
    r = pairwise_terms_ref(X, Wa, Wb, kind)
    p = ops.pairwise_terms(Xb, Wa, Wb, kind, impl="pallas", interpret=True,
                           block_rows=32, block_cols=32, lane=8)
    rel = float(jnp.linalg.norm(p.la_x - r.la_x) /
                (jnp.linalg.norm(r.la_x) + 1e-30))
    assert rel < 2e-2  # bf16 input quantization


def test_storage_bf16_matches_jnp_bf16_path():
    """bf16 STORAGE (EmbedSpec.kernel_precision): both paths quantize
    inputs through bf16 and accumulate in f32, so they agree up to
    accumulation-order noise; the f32 oracle is within bf16 distance."""
    X, Wa, Wb = _rand_problem(4, 48, 2)
    p = ops.pairwise_terms(X, Wa, Wb, "ee", impl="pallas-interpret",
                           block_rows=16, block_cols=16, lane=8,
                           storage_dtype="bfloat16")
    j = ops.pairwise_terms(X, Wa, Wb, "ee", impl="jnp",
                           storage_dtype="bfloat16")
    np.testing.assert_allclose(np.asarray(p.la_x), np.asarray(j.la_x),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(p.e_plus), float(j.e_plus), rtol=1e-3)
    r = pairwise_terms_ref(X, Wa, Wb, "ee")
    rel = float(jnp.linalg.norm(p.la_x - r.la_x) /
                (jnp.linalg.norm(r.la_x) + 1e-30))
    assert rel < 2e-2
    assert ops.last_dispatch("pairwise_terms")["storage"] == "bfloat16"


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(17, 80),
    d=st.integers(1, 6),
    kind=st.sampled_from(sorted(KINDS)),
)
def test_kernel_property_sweep(seed, n, d, kind):
    X, Wa, Wb = _rand_problem(seed, n, d)
    _check(X, Wa, Wb, kind, 16, 16)


def test_dispatch_defaults_to_ref_on_cpu():
    X, Wa, Wb = _rand_problem(2, 32, 2)
    r = ops.pairwise_terms(X, Wa, Wb, "ee")  # no pallas flags
    rr = pairwise_terms_ref(X, Wa, Wb, "ee")
    assert jnp.allclose(r.la_x, rr.la_x)


def test_unknown_kind_raises():
    X, Wa, Wb = _rand_problem(3, 16, 2)
    with pytest.raises(ValueError):
        ops.pairwise_terms(X, Wa, Wb, "bogus")
