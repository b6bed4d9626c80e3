"""Trace-time contract guards: runtime invariants the AST pass cannot see.

Three families, all usable standalone or as pytest fixtures
(tests/conftest.py registers them):

  * `assert_compile_count(expected=..)` / `CompileCounter` — count XLA
    backend compiles inside a block via jax's monitoring events and fail
    if the count is wrong.  This is how the paper's "adds nearly no
    overhead" claim is *pinned*: after warmup, the dense fused `_step`,
    the sparse epoch, the sharded epoch and every server bucket must run
    at **zero** compiles.  A retrace (shape drift, non-static Python
    arg, rebuilt closure) becomes a test failure instead of a silent
    10-100x slowdown.

  * `no_implicit_transfers()` — `jax.transfer_guard("disallow")` around
    a block.  On CPU this rejects implicit host->device uploads (Python
    scalars / numpy arrays flowing into jit, stray `jnp.asarray` of host
    data) — the transfer class that serializes the dispatch path.
    Device->host reads are zero-copy on CPU and stay allowed.

  * `no_tracer_leaks()` — `jax.checking_leaks()` around a block: a
    tracer escaping a transform (stashed on `self`, closed over by a
    callback) raises instead of surfacing later as a cryptic
    `UnexpectedTracerError` three calls away.

Warmup protocol for compile pins: eager jnp ops ALSO trigger backend
compiles (jit-of-one-op), so always run the exact call sequence once
*before* opening the counting context:

    fit()                                  # warmup: traces + compiles
    with assert_compile_count(expected=0):
        fit()                              # pinned: cache hits only
"""
from __future__ import annotations

import contextlib
import threading

import jax

# The per-compile signal: fires once for every XLA backend compilation,
# including first-touch eager ops.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_active: list["CompileCounter"] = []
_listener_installed = False


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    with _lock:
        for counter in _active:
            counter.count += 1


def _install_listener() -> None:
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _listener_installed = True


class CompileCounter:
    """Counts XLA backend compiles while registered (see
    `assert_compile_count` for the assertion wrapper)."""

    def __init__(self) -> None:
        self.count = 0

    def __enter__(self) -> "CompileCounter":
        _install_listener()
        with _lock:
            _active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        with _lock:
            _active.remove(self)


@contextlib.contextmanager
def assert_compile_count(expected: int | None = None,
                         at_most: int | None = None,
                         label: str = ""):
    """Fail unless the block performs exactly `expected` (or at most
    `at_most`) XLA backend compiles.

    Yields the live CompileCounter, so a test can also inspect
    `counter.count` mid-block.  Remember the warmup protocol (module
    docstring): run the call sequence once before pinning `expected=0`.
    """
    if (expected is None) == (at_most is None):
        raise ValueError("pass exactly one of expected= / at_most=")
    tag = f" [{label}]" if label else ""
    with CompileCounter() as counter:
        yield counter
    if expected is not None and counter.count != expected:
        raise AssertionError(
            f"compile-count contract{tag}: expected exactly {expected} "
            f"XLA compile(s), observed {counter.count} — something "
            f"retraced (shape drift, non-static python arg, or a "
            f"rebuilt jit closure)")
    if at_most is not None and counter.count > at_most:
        raise AssertionError(
            f"compile-count contract{tag}: expected <= {at_most} XLA "
            f"compile(s), observed {counter.count}")


def jit_cache_size(fn) -> int:
    """Number of traces cached for a jitted function (0 when never
    called).  Use to assert a jit is reused, not rebuilt per call."""
    try:
        return fn._cache_size()
    except AttributeError:
        return 0


@contextlib.contextmanager
def no_implicit_transfers():
    """Disallow implicit host->device transfers inside the block.

    Explicit moves (`jax.device_put`, `jax.device_get`) stay allowed —
    the contract is that every transfer on a hot path is *deliberate*.
    """
    with jax.transfer_guard("disallow"):
        yield


@contextlib.contextmanager
def no_tracer_leaks():
    """Raise on tracers escaping a jax transform inside the block."""
    with jax.checking_leaks():
        yield
