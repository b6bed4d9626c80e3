"""The benchmark harness: everything a cell needs, found by name.

    BENCHMARK.json                      cells, metrics, bounds
    bench/configs/<config>.json         a deployment: data, spec, precision
    bench/traffic/<traffic>.json        a mix: its driver and parameters
    bench/workloads/<cell>.json         what belongs to one cell alone:
                                        targets, rates and check limits
    bench/drivers/<driver>.py           runs a mix (one per kind of mix)
    bench/metrics/<metric>.py           reads one per-layer metric
    bench/work/<op>.py                  operations and bytes of one op
    bench/reference/                    the plain float64 reference

A driver exposes `run(cell) -> state`, calling `cell.begin_window()` and
`cell.end_window()` around its measured window; `end_to_end(cell, state)`
(host-clock metrics other than `setup_s`); `counters(cell, state)` (what
the per-layer readers read); `check(cell, state)`, called after the
program's state is released, which returns `{name: (value, limit)}`; and
`control(cell)`, the same checks under the cell's lower-precision control
(bench/control.py), which `correct` must refuse.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TILE_CACHE = os.path.join(CACHE_DIR, "autotune.json")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (file names may carry dots, e.g.
    `bench/metrics/pcg_iters.iter.py`)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Counts XLA backend compilations while `active`, and persistent
    compile-cache hits and misses over the whole run."""

    def __init__(self):
        self.active = False
        self.count = 0
        self.total = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        from jax._src import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.total += 1
            self.compile_s += duration
            if self.active:
                self.count += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclasses.dataclass
class Cell:
    """One run of one cell: its files, the run's arguments, and the
    window's bookkeeping."""

    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int = 1
    rehearsal: bool = False
    t_start: float = 0.0
    window_t0: float | None = None
    window_t1: float | None = None
    compiles_in_window: int = 0
    _counter: Any = None
    _ann: Any = None

    @property
    def spec_seed(self) -> int:
        """The run's seed folded into the program's 31-bit seed range."""
        return self.seed % (2 ** 31 - 2)

    def note(self, msg: str) -> None:
        """An earlier line of standard error (set-up pieces, counts)."""
        print(f"[{self.name}] {msg}", file=sys.stderr, flush=True)

    def annotate(self, name: str):
        """A host span on the profiler's clock (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def begin_window(self) -> None:
        if self.trace:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # annotations, not every call
            opts.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(WINDOW_NAME)
            self._ann.__enter__()
        if self._counter is not None:
            c = self._counter
            self.note(f"set-up compiled {c.total} programs in "
                      f"{c.compile_s:.3f} s; persistent cache {c.hits} hits, "
                      f"{c.misses} misses")
            c.count = 0
            c.active = True
        self.window_t0 = time.perf_counter()

    def end_window(self) -> None:
        self.window_t1 = time.perf_counter()
        if self._counter is not None:
            self._counter.active = False
            self.compiles_in_window = self._counter.count
        if self.trace:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    @property
    def deadline(self) -> float:
        return self.window_t0 + self.seconds


from bench.trace import WINDOW as WINDOW_NAME  # noqa: E402


def make_cell(workload: str, seed: int, seconds: float, trace: bool,
              rehearsal: bool = False) -> tuple[Cell, dict]:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[workload]
    config = load_json(BENCH, "configs", w["config"] + ".json")
    traffic = load_json(BENCH, "traffic", w["traffic"] + ".json")
    own = os.path.join(BENCH, "workloads", workload + ".json")
    if os.path.exists(own):
        traffic = {**traffic, **load_json(own)}
    cell = Cell(name=workload, config=config, traffic=traffic, seed=seed,
                seconds=seconds, trace=trace, chips=int(w["chips"]),
                rehearsal=rehearsal)
    return cell, bench


def metrics_of(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: end-to-end ones without `--trace`,
    per-layer ones with it."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader reads."""

    cell: Cell
    counters: dict
    reduction: Any        # bench.trace.Reduction of the traced window
    device_kind: str


def read_per_layer(metrics: list[dict], ctx: ReadContext) -> dict:
    out = {}
    for m in metrics:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def device_info(chips: int) -> tuple[Any, dict]:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[0], {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def configure_jax(cell: Cell) -> None:
    """The cache inside the checkout, at a fixed path, and the matmul
    precision the configuration states."""
    import jax

    if not cell.rehearsal:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    prec = cell.config.get("precision", {}).get("matmul")
    if prec:
        jax.config.update("jax_default_matmul_precision", prec)


def program_spec(cell: Cell, **overrides):
    """The `repro.api.EmbedSpec` the configuration states."""
    from repro.api import EmbedSpec
    from repro.core.linesearch import LSConfig

    cfg = cell.config
    return EmbedSpec(**cfg["spec"], ls=LSConfig(**cfg["line_search"]),
                     **overrides)


def program_control(cell: Cell, driver) -> dict:
    """A fit driver's control: the program's own bfloat16 kernel path
    switched on, the run and its check otherwise unchanged."""
    cell.config["spec"]["kernel_precision"] = "bfloat16"
    configure_jax(cell)
    state = driver.run(cell)
    driver.release(cell, state)
    return driver.check(cell, state)


def dispatch_faults(kernels: list[str]) -> list[str]:
    """Kernels of the configuration's main path that did not run as
    compiled Pallas on the chip (`repro.kernels.ops.last_dispatch`)."""
    from repro.kernels import ops

    seen = ops.last_dispatch()
    bad = []
    for k in kernels:
        d = seen.get(k)
        if d is None:
            bad.append(f"{k}: never dispatched")
        elif (d.get("interpret") or d.get("reason") == "no-tpu"
              or d.get("path") != "pallas"):
            bad.append(f"{k}: {d}")
    return bad


def run_cell(cell: Cell, bench: dict) -> dict:
    """Set up, measure, check.  Returns the result line's object."""
    driver = load_module(os.path.join(BENCH, "drivers",
                                      cell.traffic["driver"] + ".py"))
    configure_jax(cell)
    if cell.rehearsal:
        import jax

        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": 1}
    else:
        dev, device = device_info(cell.chips)
    cell._counter = CompileCounter()
    state = driver.run(cell)
    setup_s = cell.window_t0 - cell.t_start
    cell.note(f"setup_s {setup_s:.3f}, window_s {cell.window_s:.3f}, "
              f"compilations inside the window {cell.compiles_in_window}")
    device["memory_peak_bytes"] = 0 if cell.rehearsal else memory_peak(
        cell.chips)
    metrics = metrics_of(bench, cell.name, cell.trace)
    result: dict[str, Any] = {}
    if cell.trace:
        from bench import trace as tr

        red = None if cell.rehearsal else tr.reduce(tr.load(TRACE_DIR))
        if red is not None:
            top = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:25]
            cell.note("device ops: " + ", ".join(f"{k} {v:.6f} s"
                                                 for k, v in top))
        counters = driver.counters(cell, state)
        ctx = ReadContext(cell, counters, red, device["kind"])
        values = read_per_layer(metrics, ctx) if red is not None else {}
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            result["breakdown"] = tr.breakdown(red)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        e2e = driver.end_to_end(cell, state)
        e2e["setup_s"] = setup_s
        values = {m["name"]: {"value": float(e2e[m["name"]]),
                              "unit": m["unit"]} for m in metrics}
    faults = ([] if cell.rehearsal
              else dispatch_faults(cell.config.get("kernels", [])))
    for f in faults:
        cell.note(f"kernel not compiled Pallas: {f}")
    attempted, failed = state["attempted"], state["failed"]
    driver.release(cell, state)
    checks = driver.check(cell, state)
    checks["kernels_not_pallas"] = (float(len(faults)), 0.0)
    correct = all(v <= lim for v, lim in checks.values())
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": values, "device": device,
              **result,
              "checks": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checks.items()}}
    return result
