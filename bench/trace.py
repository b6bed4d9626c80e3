"""Reduction of a profiler trace to device busy time, per-operation device
time and idle gaps labelled by what the host was doing.

The traced run wraps its window in `jax.profiler.trace(<dir>)` and a host
annotation named `WINDOW`.  `load(dir)` reads the `.xplane.pb` the
profiler wrote, with nothing but JAX, into flat `Event`s;
`reduce(events)` does the arithmetic on those, so it can be checked on
hand-made events as well as on a recorded trace (bench/tests).

- Device operations are the events on the `XLA Ops` line of every
  `/device:TPU:<i>` plane.  Busy time is the union of their intervals
  inside the window, averaged over the chips that ran anything.
- An operation's device time is the sum of its events' durations,
  grouped by the event name with any `.<digits>` suffix dropped (XLA
  numbers repeated instructions: `fusion.12`, `fusion.13` -> `fusion`).
- An idle gap is a stretch of the window in which no device operation
  ran on a chip.  It is labelled by the innermost host annotation
  (program span or harness annotation) that covers the middle of the
  gap, or `(none)`.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from typing import NamedTuple

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    detail: str = ""     # a device op's text stats (its HLO op, scope)


def load(trace_dir: str) -> list[Event]:
    """Every event of the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = is_device(plane.name)
        for line in plane.lines:
            ops = device and line.name == OPS_LINE
            for ev in line.events:
                detail = (" ".join(str(v) for _, v in ev.stats
                                   if isinstance(v, str)) if ops else "")
                out.append(Event(plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns),
                                 detail))
    return out


def is_device(plane: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", plane) is not None


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(a: int, b: int, lo: int, hi: int) -> tuple[int, int] | None:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def op_name(name: str) -> str:
    return _SUFFIX.sub("", name)


class Reduction(NamedTuple):
    window_s: float
    busy_s: float                 # mean over the chips that ran anything
    n_chips: int
    op_s: dict[str, float]        # op name -> device seconds (all chips)
    gaps: list[tuple[str, float]]  # (host label, seconds), longest first
    op_detail: dict[str, str] = {}  # op name -> its first event's detail


def window_bounds(events: list[Event]) -> tuple[int, int]:
    """(start, end) ns of the `WINDOW` host annotation."""
    wins = [e for e in events if e.name == WINDOW and not is_device(e.plane)]
    if not wins:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w = max(wins, key=lambda e: e.dur_ns)
    return w.start_ns, w.start_ns + w.dur_ns


def reduce(events: list[Event]) -> Reduction:
    lo, hi = window_bounds(events)
    per_chip: dict[str, list[tuple[int, int]]] = {}
    op_ns: dict[str, int] = {}
    detail: dict[str, str] = {}
    for e in events:
        if not (is_device(e.plane) and e.line == OPS_LINE):
            continue
        iv = _clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
        if iv is None:
            continue
        per_chip.setdefault(e.plane, []).append(iv)
        key = op_name(e.name)
        op_ns[key] = op_ns.get(key, 0) + (iv[1] - iv[0])
        detail.setdefault(key, e.detail)
    host = [e for e in events
            if not is_device(e.plane) and e.name != WINDOW and e.dur_ns > 0
            and _clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)]
    busy_ns, spans = [], []
    for ivs in per_chip.values():
        merged = _union(ivs)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        spans += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    labels = _labels(host, [(a + b) // 2 for a, b in spans])
    gaps = sorted(((lab, (b - a) * 1e-9) for lab, (a, b) in
                   zip(labels, spans)), key=lambda g: -g[1])
    n = len(busy_ns)
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=(sum(busy_ns) / n * 1e-9) if n else 0.0,
        n_chips=n,
        op_s={k: v * 1e-9 for k, v in op_ns.items()},
        gaps=gaps, op_detail=detail)


def _labels(host: list[Event], times: list[int]) -> list[str]:
    """For each time, the innermost (shortest) host event covering it, or
    `(none)`, in one sweep: events enter a heap keyed by duration as the
    sweep passes their start, and leave it once they have ended and reach
    its top."""
    evs = sorted(host, key=lambda e: e.start_ns)
    heap: list[tuple[int, int, int]] = []      # (duration, end, index)
    out = ["(none)"] * len(times)
    j = 0
    for i in sorted(range(len(times)), key=times.__getitem__):
        t = times[i]
        while j < len(evs) and evs[j].start_ns <= t:
            e = evs[j]
            heapq.heappush(heap, (e.dur_ns, e.start_ns + e.dur_ns, j))
            j += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if heap:
            out[i] = evs[heap[0][2]].name
    return out


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The result line's `breakdown`: the device operations that took
    most time and the longest idle gaps, at most `top` each, summed by
    name (gaps by their host label)."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    by_label: dict[str, float] = {}
    for label, s in red.gaps:
        by_label[label] = by_label.get(label, 0.0) + s
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def kernel_seconds(red: Reduction, patterns: list[str]) -> float:
    """Device seconds of every operation whose name or detail contains
    one of `patterns` (a kernel's names as the trace shows them)."""
    return sum(s for name, s in red.op_s.items()
               if any(p in name or p in red.op_detail.get(name, "")
                      for p in patterns))
