"""Tile selection at first dispatch for the Pallas kernel suite.

ops.py consults this module whenever a caller leaves tile sizes unset:

  * a **candidate list** of `KernelConfig`s (block_rows, block_cols,
    layout, gather chunk) is generated per kernel kind in a fixed order
    of preference — the long-standing 256-row default first, then
    smaller tiles — pruned to the shapes that are legal for the request
    (hardware sublane multiples, the SMEM bound on index/weight tiles,
    divisibility constraints);
  * the **first candidate that compiles and runs** on synthetic inputs of
    the request's shape bucket wins.  A candidate that fails is skipped
    and its error kept (`failures(key)`, which ops.py copies into the
    dispatch record); when no candidate runs, the search raises with
    every candidate's error instead of guessing.  Nothing is timed: the
    tile fixes the kernels' summation order, so a pick that followed
    timing noise would make a fit's result differ from run to run (two
    runs on one v5e picked different tiles and ended 0.7% apart);
  * the winner is cached **in-process** under a key of
    (kernel kind, shape bucket, dtype, device kind, interpret) and
    optionally **on disk**: point `REPRO_AUTOTUNE_CACHE` at a JSON file
    and every process that shares it skips the compile checks.

Shape bucketing rounds N up to the next power of two (saturating at a
per-kernel cap so the synthetic inputs stay affordable), so all Ns in a
bucket share one config and the check runs once per bucket.  The first
search wins: later calls with the same key return the cached config,
which keeps dispatch deterministic within and across processes (pinned
in tests/test_kernels_autotune.py).

ops.py supplies the `runner` that executes a candidate (it owns padding
and kernel invocation, and compiles ahead of time so a search started
while an outer jit traces still compiles each candidate); this module
stays free of kernel imports so the dependency points one way.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import Any, Callable, Sequence

import jax

# -- configuration record ------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point in the search space.

    `layout` is kernel-specific: the ELL gather kernel has ``"vmem"``
    (whole X resident in VMEM) and ``"hbm"`` (X stays in HBM, neighbor
    rows DMA'd in double-buffered chunks of `chunk` rows); the pairwise
    kernel only has its ``"tiled"`` streaming layout.  `block_cols` and
    `chunk` are 0 when the kernel has no such axis.
    """

    block_rows: int
    block_cols: int = 0
    layout: str = "vmem"
    chunk: int = 0

    def to_json(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: dict[str, Any]) -> "KernelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in obj.items() if k in fields})


# -- cache ---------------------------------------------------------------------

_CACHE: dict[str, KernelConfig] = {}
_FAILED: dict[str, list[dict[str, Any]]] = {}
_DISK_LOADED_FROM: str | None = None

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"


def cache_path() -> str | None:
    return os.environ.get(CACHE_ENV) or None


def clear_cache() -> None:
    """Drop the in-process cache (the disk file, if any, is untouched and
    will be re-read on the next lookup)."""
    global _DISK_LOADED_FROM
    _CACHE.clear()
    _FAILED.clear()
    _DISK_LOADED_FROM = None


def _load_disk() -> None:
    """Merge the disk cache into the in-process one (in-process wins —
    entries this process already searched or loaded stay put)."""
    global _DISK_LOADED_FROM
    path = cache_path()
    if path is None or _DISK_LOADED_FROM == path:
        return
    _DISK_LOADED_FROM = path
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        return
    for key, obj in payload.get("entries", {}).items():
        _CACHE.setdefault(key, KernelConfig.from_json(obj))


def _save_disk() -> None:
    """Atomically rewrite the disk cache as merge(file, in-process) so
    concurrent processes lose at most their own last search, never the
    file."""
    path = cache_path()
    if path is None:
        return
    entries: dict[str, Any] = {}
    try:
        with open(path) as f:
            entries = json.load(f).get("entries", {})
    except (OSError, json.JSONDecodeError):
        pass
    entries.update({k: v.to_json() for k, v in _CACHE.items()})
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".autotune.tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump({"version": 1, "entries": entries}, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


# -- keying --------------------------------------------------------------------

# search-input caps per kernel kind: the synthetic timing inputs are
# O(bucket^2) for the pairwise kernel and O(bucket * k) for the ELL ones,
# so buckets saturate where the search itself would get expensive.  Keys
# saturate with them: every N above the cap shares the cap's config.
_BUCKET_CAP = {"pairwise": 2048, "ell": 65536, "ell_local": 65536,
               "bh": 65536}
_INTERPRET_BUCKET_CAP = {"pairwise": 512, "ell": 4096, "ell_local": 4096,
                         "bh": 4096}


def shape_bucket(kernel: str, n: int, interpret: bool) -> int:
    cap = (_INTERPRET_BUCKET_CAP if interpret else _BUCKET_CAP).get(
        kernel, 65536)
    return min(cap, max(8, 1 << max(0, int(n - 1).bit_length())))


def device_kind() -> str:
    """A stable, filename-safe id for the accelerator the config is tuned
    for (tile winners do not transfer across TPU generations)."""
    try:
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = jax.default_backend()
    return "".join(c if c.isalnum() else "-" for c in str(kind).lower())


def cache_key(kernel: str, *, n: int, k: int = 0, d: int = 0,
              dtype: str = "float32", interpret: bool = False) -> str:
    b = shape_bucket(kernel, n, interpret)
    mode = "interp" if interpret else "compiled"
    return f"{kernel}:n{b}:k{k}:d{d}:{dtype}:{device_kind()}:{mode}"


# -- candidate generation ------------------------------------------------------

# preference order: the legacy fixed 256 first, smaller tiles as fallbacks
# for the compiler's memory limits (a larger tile would not fit either)
_ELL_BLOCK_ROWS = (256, 128, 64)
_PAIRWISE_TILES = ((256, 256), (128, 128))
_HBM_CHUNK = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def fit_divisor(n: int, cap: int, step: int = 1) -> int:
    """The largest multiple of `step` that is at most `cap` and divides
    `n` (a multiple of `step`): a tile that covers `n` rows exactly."""
    t = max(step, min(cap, n) // step * step)
    while n % t:
        t -= step
    return t


def ell_candidates(*, n: int, sublane: int, layouts: Sequence[str],
                   max_rows: int | None = None,
                   max_chunk: int | None = None) -> list[KernelConfig]:
    """ELL gather candidates, legal for this request: block_rows are
    sublane multiples clipped to the (bucketed) row count and to
    `max_rows` (the SMEM bound on the index/weight tiles, ops.smem_rows);
    the "hbm" layout adds the double-buffer chunk size (a divisor of
    block_rows, at most `max_chunk`)."""
    out: list[KernelConfig] = []
    for br in _ELL_BLOCK_ROWS:
        br = _round_up(min(br, max(sublane, n)), sublane)
        if max_rows is not None:
            br = min(br, max_rows)
        for layout in layouts:
            chunk = 0
            if layout == "hbm":
                chunk = fit_divisor(br, min(_HBM_CHUNK, max_chunk or br))
            cfg = KernelConfig(block_rows=br, layout=layout, chunk=chunk)
            if cfg not in out:
                out.append(cfg)
    return out


def pairwise_candidates(*, n: int, sublane: int) -> list[KernelConfig]:
    out: list[KernelConfig] = []
    for br, bc in _PAIRWISE_TILES:
        br = _round_up(min(br, max(sublane, n)), sublane)
        bc = _round_up(min(bc, max(sublane, n)), sublane)
        cfg = KernelConfig(block_rows=br, block_cols=bc, layout="tiled")
        if cfg not in out:
            out.append(cfg)
    return out


# -- search --------------------------------------------------------------------


def _error_line(e: Exception) -> str:
    first = (str(e).strip().splitlines() or [""])[0]
    return f"{type(e).__name__}: {first}"[:300]


def failures(key: str) -> list[dict[str, Any]]:
    """The candidates this process's search for `key` saw fail, each as
    {"config": ..., "error": "<type>: <first line>"}; empty for keys
    found in the cache or searched without failures."""
    return list(_FAILED.get(key, ()))


def get_config(
    kernel: str,
    *,
    n: int,
    k: int = 0,
    d: int = 0,
    dtype: str = "float32",
    interpret: bool = False,
    candidates: Sequence[KernelConfig],
    runner: Callable[[KernelConfig, int], Callable[[], Any]],
) -> tuple[KernelConfig, bool]:
    """The config for this request: cache hit or search.

    `runner(cfg, bucket_n)` returns a zero-argument callable executing
    the kernel once at the bucket's synthetic shape under `cfg` (ops.py
    owns padding/invocation).  Returns ``(config, from_cache)``; the
    first candidate, in list order, whose runner compiles and runs is
    stored in-process and mirrored to the `REPRO_AUTOTUNE_CACHE` file
    when set.  Candidates that fail before it are listed by
    `failures(key)`; when every candidate fails the search raises
    RuntimeError with each one's error, and nothing is cached.
    """
    if not candidates:
        raise ValueError(f"no candidates for kernel {kernel!r}")
    key = cache_key(kernel, n=n, k=k, d=d, dtype=dtype, interpret=interpret)
    _load_disk()
    hit = _CACHE.get(key)
    if hit is not None:
        return hit, True

    bucket = shape_bucket(kernel, n, interpret)
    failed: list[dict[str, Any]] = []
    _FAILED[key] = failed
    for cfg in candidates:
        try:
            jax.block_until_ready(runner(cfg, bucket)())
        except Exception as e:  # a candidate the backend refuses
            failed.append({"config": cfg.to_json(), "error": _error_line(e)})
            continue
        _CACHE[key] = cfg
        _save_disk()
        return cfg, False
    detail = "; ".join(f"{f['config']}: {f['error']}" for f in failed)
    raise RuntimeError(
        f"autotune {key}: no candidate compiled and ran ({detail})")


def cached_entries() -> dict[str, KernelConfig]:
    """Snapshot of the in-process cache (for telemetry / the bench)."""
    _load_disk()
    return dict(_CACHE)
