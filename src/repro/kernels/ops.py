"""Kernel dispatch: the one entry point per hot-path primitive.

`pairwise_terms` and `ell_lap_matvec` are what the rest of the framework
calls; each is a PLAIN Python dispatcher (decisions happen at call/trace
time, outside any jit) wrapping jitted implementations:

  1. **Path**: Pallas vs the jnp oracle, decided by the `impl` knob
     ('auto' | 'pallas' | 'pallas-interpret' | 'jnp'; the legacy
     `use_pallas` bool still works) — 'auto' means Pallas on TPU, jnp
     elsewhere.
  2. **Layout + tiles**: when the caller leaves `block_rows` unset,
     autotune.py takes the first candidate of a fixed preference list
     that compiles at the request's shape bucket and caches it; the ELL
     matvec additionally
     picks its layout — whole-X-in-VMEM while X fits the VMEM budget
     (`REPRO_VMEM_X_BUDGET`, default 12 MiB), the HBM-resident
     double-buffered gather above it — so large N stays on Pallas
     instead of silently falling back.
  3. **Precision**: `storage_dtype="bfloat16"` rounds X/weights through
     bf16 while every kernel accumulates in f32; outputs are always f32.
     The pairwise kernel streams bf16 blocks; the gather kernels (ELL,
     Barnes-Hut) read rows one 32-bit word per element (a bf16 row read
     at an odd row offset does not lower), so there the rounded values
     are held as f32.  The jnp path rounds through bf16 too, so every
     path sees the same quantization.

The ELL kernels cut each row at its live length (`ell_live_lengths`:
1 + the index of its last nonzero weight), so trailing zero-weight
padding costs no time; the dispatch record says so (`row_cut`).

Every decision is recorded — never silent:

  * an active telemetry recorder gets `path`, `reason`, `layout` and the
    chosen tile config merged into its `kernel_dispatch` meta (surfaced
    by `repro.obs.report`);
  * `last_dispatch()` returns the most recent decision per kernel for
    tests and benchmarks.

On the device, each `pallas_call` is named after its jitted wrapper
(`_ell_pallas`, `_pairwise_pallas`, `_bh_pallas`): the HLO instruction
and the op's scope path carry that name in a profiler capture
(docs/observability.md).

Tile legality: requested/autotuned tile sizes are clamped to the row
count and then rounded UP to the hardware sublane multiple (8 rows for
f32, 16 for bf16), so small-N dispatch can never pick a misaligned tile;
padding (zero rows / zero-weight self-edges — exact-zero contributions
by construction, see the kernel modules) covers the remainder.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import current_tracer

from . import autotune
from .farfield import bh_interaction_pallas
from .pairwise import pairwise_terms_pallas
from .ref import (KINDS, PairwiseTerms, bh_interaction_ref,
                  ell_lap_matvec_ref, pairwise_terms_ref)
from .sparse_attractive import (ell_lap_matvec_local_pallas,
                                ell_lap_matvec_pallas,
                                ell_lap_matvec_pallas_hbm)

VMEM_X_BUDGET_ENV = "REPRO_VMEM_X_BUDGET"
_DEFAULT_VMEM_X_BUDGET = 12 * 1024 * 1024  # bytes the resident-X layout may
                                           # claim (24k f32 rows at dp=128)
# scoped VMEM a resident-table kernel asks for on top of the table: its
# streamed tiles and scratch (v5e has 128 MiB of VMEM; the compiler's
# default scoped limit would not hold a 12 MiB table plus tiles)
_VMEM_TILE_HEADROOM = 16 * 1024 * 1024
# SMEM (1 MiB on v5e) holds the double-buffered (rows, k) int32 index and
# f32 weight tiles of the gather kernels and the ELL kernel's (1, rows)
# int32 live-length tile; lanes pad to 128
_SMEM_TILE_BUDGET = 768 * 1024
# the HBM layout's (2, chunk * k, dp) f32 gather double buffer
_HBM_BUFFER_BUDGET = 4 * 1024 * 1024
# the gather kernels read and write 32-bit rows: f32 sublane tiling
_ROW_SUB = 8

IMPLS = ("auto", "pallas", "pallas-interpret", "jnp")
STORAGE_DTYPES = ("float32", "bfloat16")

_LAST: dict[str, dict] = {}


def last_dispatch(kernel: str | None = None):
    """The most recent dispatch decision (dict of path/reason/layout/
    config), per kernel or the whole registry.  Decisions are recorded at
    call/trace time — a cached XLA executable re-run does not re-dispatch."""
    return dict(_LAST) if kernel is None else _LAST.get(kernel)


def vmem_x_budget() -> int:
    try:
        return int(os.environ.get(VMEM_X_BUDGET_ENV,
                                  _DEFAULT_VMEM_X_BUDGET))
    except ValueError:
        return _DEFAULT_VMEM_X_BUDGET


def vmem_limit() -> int:
    """Scoped VMEM the resident-table kernels request: the table budget
    plus headroom for their streamed tiles."""
    return vmem_x_budget() + _VMEM_TILE_HEADROOM


def smem_tile_bytes(rows: int, k: int) -> int:
    """SMEM the gather kernels' double-buffered row tiles take at ELL
    width `k`: the (rows, k) index and weight tiles and the (1, rows)
    live-length tile, each 4-byte word, lanes padded to 128."""
    return 2 * 4 * (2 * rows * _round_up(max(k, 1), 128)
                    + _round_up(rows, 128))


def smem_rows(k: int) -> int:
    """Largest row tile (a multiple of 8) whose index, weight and length
    tiles fit the SMEM budget at ELL width `k`."""
    rows = _SMEM_TILE_BUDGET // (4 * 4 * _round_up(max(k, 1), 128))
    rows = max(_ROW_SUB, rows // _ROW_SUB * _ROW_SUB)
    while rows > _ROW_SUB and smem_tile_bytes(rows, k) > _SMEM_TILE_BUDGET:
        rows -= _ROW_SUB
    return rows


def hbm_max_chunk(k: int, dp: int) -> int:
    """Largest HBM-layout chunk whose gather double buffer fits."""
    return max(1, _HBM_BUFFER_BUDGET // (2 * k * dp * 4))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def sublane(storage_dtype) -> int:
    """Minimum legal row-tile multiple: the TPU sublane tiling is (8, 128)
    for 4-byte types and (16, 128) for 2-byte types."""
    return 16 if jnp.dtype(storage_dtype).itemsize == 2 else 8


def legal_tile(requested: int, n: int, sub: int) -> int:
    """Clamp a tile to the row count, then round UP to the sublane
    multiple (the satellite fix: `min(block_rows, n)` alone hands the
    kernel a misaligned tile whenever n is not a multiple of `sub`)."""
    return _round_up(min(requested, max(sub, n)), sub)


def _candidate(fn, *args, **static):
    """An autotune runner's thunk: `fn` compiled ahead of time for the
    concrete host arrays `args`.  Dispatch usually runs while an outer jit
    traces; a plain call would be traced into that program, so the search
    would neither compile nor time the candidate.  Compile errors raise
    here, where the search records them."""
    compiled = fn.lower(*args, **static).compile()
    return lambda: compiled(*args)


def _pad_to(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def _resolve_impl(impl, use_pallas):
    """Merge the new `impl` knob with the legacy `use_pallas` bool."""
    if impl is None:
        if use_pallas is None:
            impl = "auto"
        else:
            impl = "pallas" if use_pallas else "jnp"
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; have {IMPLS}")
    return impl


def _resolve_storage(storage_dtype):
    if storage_dtype is None:
        return "float32"
    name = jnp.dtype(storage_dtype).name
    if name not in STORAGE_DTYPES:
        raise ValueError(
            f"unsupported storage_dtype {name!r}; have {STORAGE_DTYPES}")
    return name


def _record(kernel: str, info: dict) -> None:
    """Surface the dispatch decision: module registry + telemetry meta."""
    _LAST[kernel] = info
    tracer = current_tracer()
    rec = getattr(tracer, "recorder", None) if tracer is not None else None
    if rec is not None:
        merged = dict(rec.meta.get("kernel_dispatch") or {})
        merged[kernel] = info
        rec.set_meta(kernel_dispatch=merged)


def _maybe_bf16(x: jnp.ndarray, storage: str) -> jnp.ndarray:
    """Round through the storage dtype so jnp and Pallas paths see the
    same quantization; f32 storage leaves the input untouched."""
    if storage == "bfloat16":
        return x.astype(jnp.bfloat16)
    return x


def _rows32(x: jnp.ndarray, storage: str) -> jnp.ndarray:
    """The storage-rounded values as f32: what the gather kernels read."""
    return _maybe_bf16(x.astype(jnp.float32), storage).astype(jnp.float32)


# -- ELL Laplacian matvec --------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kind", "storage"))
def _pairwise_jnp(X, Wa, Wb, kind, storage):
    if storage == "bfloat16":
        X = X.astype(jnp.bfloat16).astype(jnp.float32)
        Wa = Wa.astype(jnp.bfloat16).astype(jnp.float32)
        Wb = Wb.astype(jnp.bfloat16).astype(jnp.float32)
    return pairwise_terms_ref(X, Wa, Wb, kind)


@functools.partial(jax.jit, static_argnames=("storage",))
def _ell_jnp(X, indices, weights, storage):
    if storage == "bfloat16":
        X = X.astype(jnp.bfloat16).astype(jnp.float32)
        weights = weights.astype(jnp.bfloat16).astype(jnp.float32)
    return ell_lap_matvec_ref(X, indices, weights)


@jax.jit
def ell_live_lengths(weights: jnp.ndarray) -> jnp.ndarray:
    """(N,) int32 live length of each ELL row: 1 + the index of its last
    nonzero weight, 0 for a row without one.  The slots past it add
    exactly nothing to the row's Laplacian product, so the Pallas kernels
    skip them; zero weights before it are still visited.  A weight is
    zero when its bits are those of +0 or -0: a subnormal weight, which
    f32 arithmetic may flush to zero, still counts as live."""
    bits = jax.lax.bitcast_convert_type(weights.astype(jnp.float32),
                                        jnp.int32)
    slot = jnp.arange(1, weights.shape[1] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(bits & 0x7FFFFFFF != 0, slot, 0), axis=1)


def _live_lengths(weights, lengths, rows):
    """The given or derived live lengths, zero-padded to `rows`."""
    if lengths is None:
        lengths = ell_live_lengths(weights)
    return jnp.pad(lengths.astype(jnp.int32), (0, rows - lengths.shape[0]))


@functools.partial(jax.jit, static_argnames=(
    "block_rows", "layout", "chunk", "interpret", "lane", "storage",
    "vlimit"))
def _ell_pallas(X, indices, weights, lengths, *, block_rows, layout, chunk,
                interpret, lane, storage, vlimit):
    n, d = X.shape
    n_pad = _round_up(n, block_rows)
    dp = max(lane, d)
    Xp = _pad_to(_rows32(X, storage), n_pad, dp)
    idx_p = jnp.pad(indices.astype(jnp.int32), ((0, n_pad - n), (0, 0)))
    w_p = _pad_to(_rows32(weights, storage), n_pad, weights.shape[1])
    len_p = _live_lengths(weights, lengths, n_pad)
    if layout == "hbm":
        out = ell_lap_matvec_pallas_hbm(
            Xp, idx_p, w_p, len_p, block_rows=block_rows, chunk=chunk,
            interpret=interpret)
    else:
        out = ell_lap_matvec_pallas(
            Xp, idx_p, w_p, len_p, block_rows=block_rows,
            vmem_limit_bytes=vlimit, interpret=interpret)
    return out[:n, :d]


def _ell_decide(n, k, d, impl, interpret, layout, storage, lane):
    """(path, reason, layout, interpret) for an ELL matvec request."""
    if impl == "jnp":
        return "jnp", "forced-off", None, False
    if impl == "auto":
        if not _on_tpu():
            return "jnp", "no-tpu", None, False
        reason = "tpu-default"
    else:
        reason = "forced-on"
    if interpret is None:
        interpret = impl == "pallas-interpret" or not _on_tpu()
    if layout is None:
        resident = _round_up(n, _ROW_SUB) * max(lane, d) * 4
        if resident > vmem_x_budget():
            layout, reason = "hbm", "vmem-cap"
        else:
            layout = "vmem"
    return "pallas", reason, layout, interpret


def ell_lap_matvec(
    X: jnp.ndarray,          # (N, d)
    indices: jnp.ndarray,    # (N, k) int32
    weights: jnp.ndarray,    # (N, k)
    *,
    impl: str | None = None,
    use_pallas: bool | None = None,
    block_rows: int | None = None,
    layout: str | None = None,
    chunk: int | None = None,
    interpret: bool | None = None,
    lane: int = 128,
    storage_dtype=None,
    lengths: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Directed ELL Laplacian product L(A) X; see kernels/ref.py for the
    contract and the module docstring for the dispatch ladder.  Leave
    `block_rows`/`layout`/`chunk` unset to let the autotuner pick them.

    The Pallas path cuts each row at its live length: `lengths` as
    `ell_live_lengths(weights)` gives them, handed in by callers that
    apply one graph many times (sparse/linalg.make_sd_operator), derived
    here from `weights` when absent.  The jnp path ignores them."""
    impl = _resolve_impl(impl, use_pallas)
    storage = _resolve_storage(storage_dtype)
    n, d = X.shape
    k = indices.shape[1]
    path, reason, lay, interp = _ell_decide(
        n, k, d, impl, interpret, layout, storage, lane)

    if path == "jnp":
        info = {"path": "jnp", "reason": reason, "storage": storage}
        _record("ell_lap_matvec", info)
        return _ell_jnp(X, indices, weights, storage)

    max_rows = smem_rows(k)
    max_chunk = hbm_max_chunk(k, max(lane, d))
    vlimit = vmem_limit()
    autotuned = cache_hit = False
    failed: list = []
    if block_rows is not None:
        br = min(legal_tile(block_rows, n, _ROW_SUB), max_rows)
        ch = autotune.fit_divisor(br, min(chunk or 8, max_chunk))
    else:
        cands = autotune.ell_candidates(
            n=n, sublane=_ROW_SUB, layouts=[lay], max_rows=max_rows,
            max_chunk=max_chunk)

        def runner(cfg, bucket_n):
            return _candidate(
                _ell_pallas, np.ones((bucket_n, d), np.float32),
                np.zeros((bucket_n, k), np.int32),
                np.ones((bucket_n, k), np.float32), None,
                block_rows=cfg.block_rows, layout=cfg.layout,
                chunk=cfg.chunk, interpret=interp, lane=lane,
                storage=storage, vlimit=vlimit)

        key = dict(n=n, k=k, d=d, dtype=storage, interpret=interp)
        cfg, cache_hit = autotune.get_config(
            "ell", **key, candidates=cands, runner=runner)
        failed = autotune.failures(autotune.cache_key("ell", **key))
        autotuned = True
        br = min(legal_tile(cfg.block_rows, n, _ROW_SUB), max_rows)
        ch = autotune.fit_divisor(br, min(cfg.chunk or 8, max_chunk))

    info = {"path": "pallas", "reason": reason, "layout": lay,
            "storage": storage, "block_rows": br,
            "chunk": ch if lay == "hbm" else 0, "interpret": interp,
            "autotuned": autotuned, "cache_hit": cache_hit,
            "failed": failed, "row_cut": "live-length",
            "lengths": "derived" if lengths is None else "given"}
    _record("ell_lap_matvec", info)
    return _ell_pallas(X, indices, weights, lengths, block_rows=br,
                       layout=lay, chunk=ch, interpret=interp, lane=lane,
                       storage=storage, vlimit=vlimit)


# -- fused pairwise terms --------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "kind", "block_rows", "block_cols", "interpret", "lane", "storage"))
def _pairwise_pallas(X, Wa, Wb, *, kind, block_rows, block_cols, interpret,
                     lane, storage):
    n, d = X.shape
    # N must be a multiple of BOTH tile sizes — lcm, not sequential
    # rounding (which loses the first multiple for non-nested tile pairs)
    n_pad = _round_up(n, math.lcm(block_rows, block_cols))
    dp = max(lane, d)
    Xp = _pad_to(_maybe_bf16(X.astype(jnp.float32), storage), n_pad, dp)
    Wap = _pad_to(_maybe_bf16(Wa.astype(jnp.float32), storage),
                  n_pad, n_pad)
    Wbp = _pad_to(_maybe_bf16(Wb.astype(jnp.float32), storage),
                  n_pad, n_pad)
    t = pairwise_terms_pallas(
        Xp, Wap, Wbp, kind,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret)
    return PairwiseTerms(
        la_x=t.la_x[:n, :d], lb_x=t.lb_x[:n, :d], e_plus=t.e_plus, s=t.s)


def pairwise_terms(
    X: jnp.ndarray,
    Wa: jnp.ndarray,
    Wb: jnp.ndarray,
    kind: str,
    *,
    impl: str | None = None,
    use_pallas: bool | None = None,
    block_rows: int | None = None,
    block_cols: int | None = None,
    interpret: bool | None = None,
    lane: int = 128,
    storage_dtype=None,
) -> PairwiseTerms:
    """Fused pairwise terms; see kernels/ref.py for the contract and the
    module docstring for the dispatch ladder."""
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}")
    impl = _resolve_impl(impl, use_pallas)
    storage = _resolve_storage(storage_dtype)
    n, d = X.shape

    if impl == "jnp" or (impl == "auto" and not _on_tpu()):
        reason = "forced-off" if impl == "jnp" else "no-tpu"
        info = {"path": "jnp", "reason": reason, "storage": storage}
        _record("pairwise_terms", info)
        return _pairwise_jnp(X, Wa, Wb, kind, storage)

    reason = "tpu-default" if impl == "auto" else "forced-on"
    if interpret is None:
        interpret = impl == "pallas-interpret" or not _on_tpu()
    sub = sublane(storage)
    autotuned = cache_hit = False
    failed: list = []
    if block_rows is not None or block_cols is not None:
        br = legal_tile(block_rows or 256, n, sub)
        bc = legal_tile(block_cols or br, n, sub)
    else:
        cands = autotune.pairwise_candidates(n=n, sublane=sub)

        def runner(cfg, bucket_n):
            W = np.ones((bucket_n, bucket_n), np.float32)
            return _candidate(
                _pairwise_pallas, np.ones((bucket_n, d), np.float32), W, W,
                kind=kind, block_rows=cfg.block_rows,
                block_cols=cfg.block_cols, interpret=interpret, lane=lane,
                storage=storage)

        key = dict(n=n, d=d, dtype=storage, interpret=interpret)
        cfg, cache_hit = autotune.get_config(
            "pairwise", **key, candidates=cands, runner=runner)
        failed = autotune.failures(autotune.cache_key("pairwise", **key))
        autotuned = True
        br = legal_tile(cfg.block_rows, n, sub)
        bc = legal_tile(cfg.block_cols, n, sub)

    info = {"path": "pallas", "reason": reason, "layout": "tiled",
            "storage": storage, "block_rows": br, "block_cols": bc,
            "interpret": interpret, "autotuned": autotuned,
            "cache_hit": cache_hit, "failed": failed}
    _record("pairwise_terms", info)
    return _pairwise_pallas(X, Wa, Wb, kind=kind, block_rows=br,
                            block_cols=bc, interpret=interpret,
                            lane=lane, storage=storage)


# -- Barnes-Hut cell interaction -------------------------------------------------

@functools.partial(jax.jit, static_argnames=("kind", "storage"))
def _bh_jnp(X, idx, w, table, kind, storage):
    if storage == "bfloat16":
        # w stays f32: it carries cell occupancies (exact small integers)
        X = X.astype(jnp.bfloat16).astype(jnp.float32)
        table = table.astype(jnp.bfloat16).astype(jnp.float32)
    return bh_interaction_ref(X, idx, w, table, kind)


@functools.partial(jax.jit, static_argnames=(
    "kind", "block_rows", "interpret", "lane", "storage", "vlimit"))
def _bh_pallas(X, idx, w, table, *, kind, block_rows, interpret, lane,
               storage, vlimit):
    n, d = X.shape
    n_pad = _round_up(n, block_rows)
    dp = max(lane, d)
    Xp = _pad_to(_rows32(X, storage), n_pad, dp)
    tab_p = _pad_to(_rows32(table, storage), table.shape[0], dp)
    idx_p = jnp.pad(idx.astype(jnp.int32), ((0, n_pad - n), (0, 0)))
    w_p = _pad_to(w.astype(jnp.float32), n_pad, w.shape[1])
    s, F = bh_interaction_pallas(
        Xp, idx_p, w_p, tab_p, kind, block_rows=block_rows,
        vmem_limit_bytes=vlimit, interpret=interpret)
    return s[:n], F[:n, :d]


def bh_interaction(
    X: jnp.ndarray,          # (N, d)
    idx: jnp.ndarray,        # (N, W) int32, rows of `table`
    w: jnp.ndarray,          # (N, W) slot weights (0 = masked)
    table: jnp.ndarray,      # (M, d) interaction targets
    kind: str,
    *,
    impl: str | None = None,
    use_pallas: bool | None = None,
    block_rows: int | None = None,
    interpret: bool | None = None,
    lane: int = 128,
    storage_dtype=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Barnes-Hut cell interaction (s_n, F_n); see kernels/ref.py
    `bh_interaction_ref` for the contract and the module docstring for
    the dispatch ladder.  The Pallas path keeps the whole target table
    resident in VMEM, so requests whose table exceeds the VMEM budget
    fall back to jnp with reason ``"vmem-cap"`` (there is no HBM layout
    for this kernel — tables that big mean the near field is being fed
    raw X, which the jnp gather handles fine)."""
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}")
    impl = _resolve_impl(impl, use_pallas)
    storage = _resolve_storage(storage_dtype)
    n, d = X.shape
    width = idx.shape[1]
    m = table.shape[0]
    dp = max(lane, d)

    reason = None
    if impl == "jnp" or (impl == "auto" and not _on_tpu()):
        reason = "forced-off" if impl == "jnp" else "no-tpu"
    elif m * dp * 4 > vmem_x_budget():
        reason = "vmem-cap"
    if reason is not None:
        info = {"path": "jnp", "reason": reason, "storage": storage}
        _record("bh_interaction", info)
        return _bh_jnp(X, idx, w, table, kind, storage)

    reason = "tpu-default" if impl == "auto" else "forced-on"
    if interpret is None:
        interpret = impl == "pallas-interpret" or not _on_tpu()
    max_rows = smem_rows(width)
    vlimit = vmem_limit()
    autotuned = cache_hit = False
    failed: list = []
    if block_rows is not None:
        br = min(legal_tile(block_rows, n, _ROW_SUB), max_rows)
    else:
        cands = autotune.ell_candidates(
            n=n, sublane=_ROW_SUB, layouts=["vmem"], max_rows=max_rows)

        def runner(cfg, bucket_n):
            return _candidate(
                _bh_pallas, np.ones((bucket_n, d), np.float32),
                np.zeros((bucket_n, width), np.int32),
                np.ones((bucket_n, width), np.float32),
                np.ones((m, d), np.float32), kind=kind,
                block_rows=cfg.block_rows, interpret=interpret, lane=lane,
                storage=storage, vlimit=vlimit)

        key = dict(n=n, k=width, d=d, dtype=storage, interpret=interpret)
        cfg, cache_hit = autotune.get_config(
            "bh", **key, candidates=cands, runner=runner)
        failed = autotune.failures(autotune.cache_key("bh", **key))
        autotuned = True
        br = min(legal_tile(cfg.block_rows, n, _ROW_SUB), max_rows)

    info = {"path": "pallas", "reason": reason, "layout": "vmem",
            "storage": storage, "block_rows": br, "interpret": interpret,
            "autotuned": autotuned, "cache_hit": cache_hit,
            "failed": failed}
    _record("bh_interaction", info)
    return _bh_pallas(X, idx, w, table, kind=kind, block_rows=br,
                      interpret=interpret, lane=lane, storage=storage,
                      vlimit=vlimit)


# -- sharded local-rows ELL matvec -----------------------------------------------


def resolve_local_ell(nb: int, k: int, d: int, *, n_rep: int,
                      impl: str = "auto", storage_dtype=None,
                      interpret: bool | None = None, lane: int = 128):
    """Build-time dispatch for the shard_map-local ELL kernel
    (sparse/sharding.py): returns ``None`` when the jnp per-shard gather
    should be used, else a dict of static kwargs for
    `ell_lap_matvec_local` — the decision must be made OUTSIDE the
    shard_map trace, where the autotuner may still run eagerly.  The
    kernel keeps the replicated X (`n_rep` rows) resident in VMEM and has
    no HBM layout, so above the VMEM budget it routes to the jnp gather
    with reason ``"vmem-cap"``.

    `block_rows` is the autotuned pick rounded DOWN to a divisor of `nb`
    (the local grid must tile the shard exactly, and the BlockSpec row
    translation needs row0 % block_rows == 0 — sharding.py pads nb to a
    sublane multiple)."""
    impl = _resolve_impl(impl, None)
    storage = _resolve_storage(storage_dtype)
    reason = None
    if impl == "jnp" or (impl == "auto" and not _on_tpu()):
        reason = "forced-off" if impl == "jnp" else "no-tpu"
    elif _round_up(n_rep, _ROW_SUB) * max(lane, d) * 4 > vmem_x_budget():
        reason = "vmem-cap"
    if reason is not None:
        _record("ell_lap_matvec_local",
                {"path": "jnp", "reason": reason, "storage": storage})
        return None
    if interpret is None:
        interpret = impl == "pallas-interpret" or not _on_tpu()
    max_rows = smem_rows(k)
    vlimit = vmem_limit()
    cands = autotune.ell_candidates(
        n=nb, sublane=_ROW_SUB, layouts=["vmem"], max_rows=max_rows)

    def runner(cfg, bucket_n):
        rows = _round_up(bucket_n, cfg.block_rows)   # the grid tiles rows
        fn = jax.jit(functools.partial(
            ell_lap_matvec_local_pallas, block_rows=cfg.block_rows,
            vmem_limit_bytes=vlimit, interpret=interpret))
        return _candidate(
            fn, np.ones((rows, max(lane, d)), np.float32),
            np.zeros((rows, k), np.int32), np.ones((rows, k), np.float32),
            np.full(rows, k, np.int32), np.int32(0))

    key = dict(n=nb, k=k, d=d, dtype=storage, interpret=interpret)
    cfg, cache_hit = autotune.get_config(
        "ell_local", **key, candidates=cands, runner=runner)
    br = autotune.fit_divisor(
        nb, min(legal_tile(cfg.block_rows, nb, _ROW_SUB), max_rows), _ROW_SUB)
    info = {"path": "pallas", "reason": "forced-on" if impl != "auto"
            else "tpu-default", "layout": "vmem", "storage": storage,
            "block_rows": br, "interpret": interpret, "autotuned": True,
            "cache_hit": cache_hit,
            "failed": autotune.failures(autotune.cache_key("ell_local",
                                                           **key)),
            "row_cut": "live-length"}
    _record("ell_lap_matvec_local", info)
    return {"block_rows": br, "interpret": interpret, "storage": storage}


def ell_lap_matvec_local(X_rep, indices, weights, row0, *, block_rows,
                         interpret, storage, lane: int = 128, lengths=None):
    """Local rows of L(A) X inside a shard_map body, via the
    scalar-prefetch translated kernel.  Static kwargs come from
    `resolve_local_ell` (called at build time); this function is safe to
    trace inside shard_map (no dispatch, no autotune).  Rows are cut at
    their live lengths, given or derived from `weights` as in
    `ell_lap_matvec`."""
    d = X_rep.shape[1]
    dp = max(lane, d)
    Xk = jnp.pad(_rows32(X_rep, storage), ((0, 0), (0, dp - d)))
    out = ell_lap_matvec_local_pallas(
        Xk, indices.astype(jnp.int32), _rows32(weights, storage),
        _live_lengths(weights, lengths, weights.shape[0]), row0,
        block_rows=block_rows, interpret=interpret,
        vmem_limit_bytes=vmem_limit())
    return out[:, :d]
