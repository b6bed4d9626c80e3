"""The traced window put down to program phases: device time by the
program's host spans and by its device scopes (docs/observability.md).

The program names its phases twice.  Host spans (`repro.obs.span`,
mirrored into the profiler as annotations when a traced fit runs with
`Telemetry(jax_annotations=True)`) say what the host was doing:
`solve-iter` with `step`/`fetch` or `direction`/`line-search`/`grad`
inside it, then `iter-host`.  Device scopes (`jax.named_scope`) ride in each HLO
instruction's metadata (`op_name`): `objective`, `direction-solve`,
`laplacian/forward`, `laplacian/reverse`.

A TPU trace names a device op by its HLO instruction and carries no
metadata, so an op's scope path comes from the compiled module: the
profiler keeps each program's HLO proto in its `/host:metadata` plane,
which `ProfileData` does not expose.  `hlo_op_names` reads those protos
from the `.xplane.pb` itself, and each op is looked up by the program
(the `XLA Modules` event around it) and its instruction name.

`load(ctx)` reads the traced run's profile once for the run's read
context (events through `bench.trace.load`, before the harness removes
the profile) and reduces it to a `Split`; `use(ctx, events, op_names)`
gives it hand-made events.  Where the program lacks a span or a scope
(an older checkout), the functions here find nothing and the readers
return None.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple

from bench import trace
from bench.harness import TRACE_DIR

#: host spans of the program and annotations of the harness that label
#: idle time in the stderr note (innermost first wins)
PROGRAM_SPANS = ("bench/fit", "graph-build", "spectral-init", "setup",
                 "compile", "solve-iter", "step", "fetch", "direction",
                 "line-search", "grad", "iter-host", "checkpoint")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
_INSTRUCTION = re.compile(r"%?([^\s=]+)")


class Busy(NamedTuple):
    """One chip's merged op intervals, with the busy time before each."""
    starts: list[int]
    ends: list[int]
    before: list[int]

    @classmethod
    def of(cls, intervals: list[tuple[int, int]]) -> "Busy":
        merged = trace._union(intervals)
        before, total = [], 0
        for a, b in merged:
            before.append(total)
            total += b - a
        return cls([a for a, _ in merged], [b for _, b in merged], before)

    def until(self, t: int) -> int:
        """Busy nanoseconds before `t`."""
        j = bisect.bisect_right(self.starts, t) - 1
        if j < 0:
            return 0
        return self.before[j] + min(t, self.ends[j]) - self.starts[j]


class Split(NamedTuple):
    lo: int                                   # window bounds, ns
    hi: int
    busy: dict[str, Busy]                     # chip -> its busy intervals
    ops: list[tuple[str, int, int, str]]      # (chip, start, end, scopes)
    host: list[trace.Event]                   # host events inside the window
    ambiguous: int = 0                        # ops left without a scope


def split(events: list[trace.Event],
          op_names: dict[str, dict[str, str]] | None = None) -> Split:
    """`events` within the window; each device op keeps the text its
    scopes are read from: its detail and, from `op_names` (module name ->
    {instruction: op_name}), the op_name of its instruction in the
    program running around it."""
    lo, hi = trace.window_bounds(events)
    lookup = _lookup(op_names or {})
    modules: dict[str, list[tuple[int, int, str]]] = {}
    for e in events:
        if trace.is_device(e.plane) and e.line == MODULES_LINE:
            modules.setdefault(e.plane, []).append(
                (e.start_ns, e.start_ns + e.dur_ns, e.name))
    mod_starts = {}
    for chip, mods in modules.items():
        mods.sort()
        mod_starts[chip] = [m[0] for m in mods]
    intervals: dict[str, list[tuple[int, int]]] = {}
    ops = []
    ambiguous = 0
    for e in events:
        if not (trace.is_device(e.plane) and e.line == trace.OPS_LINE):
            continue
        iv = trace._clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
        if iv is None:
            continue
        intervals.setdefault(e.plane, []).append(iv)
        scope = ""
        j = bisect.bisect_right(mod_starts.get(e.plane, []), e.start_ns) - 1
        if j >= 0 and e.start_ns < modules[e.plane][j][1]:
            m = _INSTRUCTION.match(e.name)
            names = lookup(modules[e.plane][j][2])
            op = names.get(m.group(1), "") if m else ""
            ambiguous += op is None
            scope = op or ""
        ops.append((e.plane, iv[0], iv[1], f"{e.detail} {scope}"))
    host = [e for e in events
            if not trace.is_device(e.plane) and e.name != trace.WINDOW
            and trace._clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)]
    return Split(lo, hi, {c: Busy.of(v) for c, v in intervals.items()},
                 ops, host, ambiguous)


def _lookup(op_names: dict[str, dict[str, str]]):
    """Module name -> its {instruction: op_name}.  A module's event and
    its proto may differ in the id after the name (`jit_solve(12)`): an
    unmatched name then takes, for each instruction, the op_name that
    every proto of the same base name holding it agrees on.  Instruction
    names are unique only within one program, so where those protos
    disagree the instruction maps to None and its op takes no scope."""
    candidates: dict[str, dict[str, set[str]]] = {}
    for name, names in op_names.items():
        base = candidates.setdefault(name.split("(")[0], {})
        for inst, op in names.items():
            base.setdefault(inst, set()).add(op)
    by_base = {base: {inst: next(iter(ops)) if len(ops) == 1 else None
                      for inst, ops in insts.items()}
               for base, insts in candidates.items()}

    def lookup(module: str) -> dict[str, str | None]:
        if module in op_names:
            return op_names[module]
        return by_base.get(module.split("(")[0], {})
    return lookup


# -- HLO protos from the profile --------------------------------------------
# field numbers: tsl/profiler/protobuf/xplane.proto (XSpace, XPlane,
# XEventMetadata, XStat, XStatMetadata) and xla/service/hlo.proto
# (HloProto, HloModuleProto, HloComputationProto, HloInstructionProto;
# OpMetadata in xla/xla_data.proto)


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of a protobuf message: varints as ints,
    length-delimited values as memoryviews, fixed-width ones as None."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _first(buf, field: int):
    return next((v for f, v in _fields(buf) if f == field), None)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace") if v is not None else ""


def _instruction_op_names(hlo_proto) -> dict[str, str]:
    names = {}
    module = _first(hlo_proto, 1)                       # HloProto.hlo_module
    for f, comp in _fields(module if module is not None else b""):
        if f != 3:                                      # .computations
            continue
        for g, inst in _fields(comp):
            if g != 2:                                  # .instructions
                continue
            name = meta = None
            for h, v in _fields(inst):
                if h == 1:                              # .name
                    name = _text(v)
                elif h == 7:                            # .metadata
                    meta = v
            if name and meta is not None:
                op = _text(_first(meta, 2))             # OpMetadata.op_name
                if op:
                    names[name] = op
    return names


def hlo_op_names(trace_dir: str) -> dict[str, dict[str, str]]:
    """Module name -> {HLO instruction: op_name}, from the HLO protos in
    the newest `.xplane.pb` under `trace_dir`; empty where the profile
    kept none."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {}
    with open(paths[-1], "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(space):
        if f != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        proto_stat, events = set(), []
        for g, v in _fields(plane):
            if g == 5:                          # stat_metadata map entry
                md = _first(v, 2)
                if md is not None and _text(_first(md, 2)) == "Hlo Proto":
                    proto_stat.add(_first(md, 1))
            elif g == 4:                        # event_metadata map entry
                events.append(_first(v, 2))
        for em in events:
            if em is None:
                continue
            name, protos = "", []
            for g, v in _fields(em):
                if g == 2:                      # XEventMetadata.name
                    name = _text(v)
                elif g == 5:                    # .stats: XStat
                    stat = dict(_fields(v))
                    if stat.get(1) in proto_stat and stat.get(6) is not None:
                        protos.append(stat[6])  # .bytes_value
            for p in protos:
                out.setdefault(name, {}).update(_instruction_op_names(p))
    return out


# -- reading -----------------------------------------------------------------


def use(ctx, events: list[trace.Event],
        op_names: dict[str, dict[str, str]] | None = None) -> Split:
    """Give `ctx` these events in place of the run's profile (tests)."""
    ctx.program_split = split(events, op_names)
    return ctx.program_split


def load(ctx) -> Split | None:
    """The traced window's `Split`, read once for `ctx`; None where the
    run was not traced or left no profile."""
    if "program_split" in vars(ctx):
        return ctx.program_split
    sp = None
    if ctx.reduction is not None:
        try:
            op_names = hlo_op_names(TRACE_DIR)
        except (ValueError, IndexError) as e:     # a proto it cannot read
            ctx.cell.note(f"no scopes: the profile's HLO protos: {e!r}")
            op_names = {}
        try:
            sp = split(trace.load(TRACE_DIR), op_names)
        except (FileNotFoundError, ValueError):
            sp = None
        if sp is not None:
            ctx.cell.note("idle by innermost program span: " + ", ".join(
                f"{k} {v:.6f} s" for k, v in idle_by_span(sp)))
        if sp is not None and sp.ambiguous:
            ctx.cell.note(f"{sp.ambiguous} device ops took no scope: "
                          "programs of one name disagree on them")
    ctx.program_split = sp
    return sp


def idle_s(sp: Split, a: int, b: int) -> float:
    """Seconds of [a, b] in which no operation ran on the device, mean
    over the chips that ran anything."""
    a, b = max(a, sp.lo), min(b, sp.hi)
    if b <= a or not sp.busy:
        return 0.0
    idle = [(b - a) - (bz.until(b) - bz.until(a)) for bz in sp.busy.values()]
    return sum(idle) / len(idle) * 1e-9


def window_s(sp: Split) -> float:
    return (sp.hi - sp.lo) * 1e-9


def _scope_re(scope: str) -> re.Pattern:
    return re.compile(r"(?:^|[/\s])" + re.escape(scope) + r"(?:[/\s]|$)")


def scope_s(sp: Split, scope: str) -> float:
    """Device seconds under the scope `scope`: the union of the intervals
    of every operation whose scope path holds it (a `while` op and the
    kernels nested in it count once), mean over the chips that ran
    anything."""
    pat = _scope_re(scope)
    per_chip: dict[str, list[tuple[int, int]]] = {}
    for chip, a, b, text in sp.ops:
        if pat.search(text):
            per_chip.setdefault(chip, []).append((a, b))
    if not sp.busy:
        return 0.0
    total = sum(sum(y - x for x, y in trace._union(v))
                for v in per_chip.values())
    return total / len(sp.busy) * 1e-9


def spans(sp: Split, name: str) -> list[trace.Event]:
    return sorted((e for e in sp.host if e.name == name),
                  key=lambda e: e.start_ns)


def fit_phases(sp: Split) -> list[tuple[int, int, int | None]]:
    """(start, first `solve-iter` start, last `iter-host` end) of every
    `bench/fit` annotation whose loop the trace shows; the end is None
    where the program has no `iter-host` span."""
    iters = [e.start_ns for e in spans(sp, "solve-iter")]
    tails = spans(sp, "iter-host")
    tail_starts = [e.start_ns for e in tails]
    out = []
    for f in spans(sp, "bench/fit"):
        end = f.start_ns + f.dur_ns
        i = bisect.bisect_left(iters, f.start_ns)
        if i == len(iters) or iters[i] >= end:
            continue
        j = bisect.bisect_left(tail_starts, end) - 1
        last = (tails[j].start_ns + tails[j].dur_ns
                if j >= 0 and tail_starts[j] >= f.start_ns else None)
        out.append((f.start_ns, iters[i], last))
    return out


def per_iter_ms(ctx, scope: str):
    """Device ms under `scope` per iteration of the window, or None."""
    sp = load(ctx)
    iters = ctx.counters.get("iters")
    if sp is None or not iters:
        return None
    s = scope_s(sp, scope)
    return 1e3 * s / iters if s > 0 else None


def idle_by_span(sp: Split) -> list[tuple[str, float]]:
    """Idle seconds of the window by the innermost program span covering
    the middle of each gap, longest first (mean over chips)."""
    host = [e for e in sp.host if e.name in PROGRAM_SPANS and e.dur_ns > 0]
    gaps = []
    for bz in sp.busy.values():
        edges = [sp.lo] + [x for iv in zip(bz.starts, bz.ends)
                           for x in iv] + [sp.hi]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    labels = trace._labels(host, [(a + b) // 2 for a, b in gaps])
    out: dict[str, float] = {}
    n = max(len(sp.busy), 1)
    for lab, (a, b) in zip(labels, gaps):
        out[lab] = out.get(lab, 0.0) + (b - a) * 1e-9 / n
    return sorted(out.items(), key=lambda kv: -kv[1])
