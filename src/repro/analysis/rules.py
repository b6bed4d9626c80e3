"""The RPR rule set: JAX/Pallas-aware AST lints, tailored to this codebase.

Every rule encodes an invariant that a stock linter cannot see because it
is about HOW this repo uses jax, not about Python:

  RPR001  host-sync in hot paths — `.item()` / `float()` / `int()` /
          `np.asarray` / `jax.device_get` / `jax.devices` inside the
          engine loop, jitted step bodies, or the per-iteration
          diagnostics extraction.  Each one is a device round-trip paid
          every iteration (or a trace error inside jit) — the exact
          overhead class the telemetry on/off gate (≤1.05) budgets for.
  RPR002  PRNG key reuse — the same key object consumed by two
          `jax.random.*` draws without an intervening `split`/`fold_in`
          reassignment produces correlated samples (the EE negative
          draws would silently lose their unbiasedness).
  RPR003  jit retrace hazards — str/bool-valued parameters of jitted
          functions not declared in `static_argnames` (bool retraces
          per value; str is a trace error), mutable default args on
          jitted functions, and closure capture of module-level mutable
          config.  Retraces are how "adds nearly no overhead to the
          gradient" silently dies.
  RPR004  Pallas tile constraints — `BlockSpec` dimension literals that
          are not sublane multiples (8 rows for f32, 16 for bf16 — the
          PR-6 `legal_tile` fix, now enforced at the source level), and
          `memory_space=` passed as a raw string instead of the
          version-shimmed `pltpu`/`pl` symbols.
  RPR005  bf16 reductions without an f32 accumulator — reductions /
          contractions over a value that took an `.astype(bfloat16)`
          path need `dtype=`/`preferred_element_type=jnp.float32`
          (kernels upcast AFTER the gather; accumulating in bf16 loses
          the mixed-precision parity the kernel gate pins at 1e-5).
  RPR006  `DeprecationWarning` without `stacklevel=2` — the warning
          must point at CALLER code, or a user cannot find which of
          their call sites to migrate.
  RPR007  `span(...)` not used as a context manager — a bare call
          creates the span object and drops it: nothing is timed, and
          the trace silently loses the phase.

Each rule is a callable `rule(tree, path, src) -> list[Finding]`; the
driver (lint.py) parses once and runs all rules per file.
"""
from __future__ import annotations

import ast
from typing import Callable

from .lint import Finding

# -- shared AST helpers ----------------------------------------------------------


def qualname(node: ast.AST) -> str:
    """Dotted name of a call target: `jax.random.normal`, `np.asarray`,
    `float`.  Empty string for non-name expressions (subscripts, calls)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _walk_scopes(tree: ast.Module):
    """Yield (scope_name, func_node, parents) for every function in the
    module, where scope_name is the dotted lexical path (e.g.
    `fit_loop.<locals>.save` collapses to `fit_loop.save`)."""
    def rec(node, prefix, parents):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = f"{prefix}.{child.name}" if prefix else child.name
                yield name, child, parents
                yield from rec(child, name, parents + [child])
            elif isinstance(child, ast.ClassDef):
                name = f"{prefix}.{child.name}" if prefix else child.name
                yield from rec(child, name, parents)
            else:
                yield from rec(child, prefix, parents)

    yield from rec(tree, "", [])


def _decorator_is_jit(dec: ast.AST) -> bool:
    """True for @jax.jit, @jit, @functools.partial(jax.jit, ...) and
    @partial(jax.jit, ...) decorators."""
    if qualname(dec) in ("jax.jit", "jit"):
        return True
    if isinstance(dec, ast.Call):
        q = qualname(dec.func)
        if q in ("jax.jit", "jit"):
            return True
        if q in ("functools.partial", "partial") and dec.args:
            return qualname(dec.args[0]) in ("jax.jit", "jit")
    return False


def _jit_static_argnames(dec: ast.AST) -> set[str]:
    """Literal `static_argnames` strings of a jit decorator (empty when
    the decorator takes none or they are not literals)."""
    if not isinstance(dec, ast.Call):
        return set()
    for kw in dec.keywords:
        if kw.arg == "static_argnames":
            out = set()
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    out.add(el.value)
            return out
    return set()


def _jitted_functions(tree: ast.Module):
    """(func_node, static_argnames) for every function the module jits:
    decorated defs, plus defs passed by name to a `jax.jit(...)` call."""
    by_name = {}
    for _, fn, _ in _walk_scopes(tree):
        by_name.setdefault(fn.name, fn)
    out = []
    seen: set[int] = set()
    for _, fn, _ in _walk_scopes(tree):
        for dec in fn.decorator_list:
            if _decorator_is_jit(dec):
                if id(fn) not in seen:
                    seen.add(id(fn))
                    out.append((fn, _jit_static_argnames(dec)))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and qualname(node.func)
                in ("jax.jit", "jit") and node.args):
            target = node.args[0]
            if isinstance(target, ast.Name) and target.id in by_name:
                fn = by_name[target.id]
                if id(fn) not in seen:
                    seen.add(id(fn))
                    out.append((fn, _jit_static_argnames(node)))
    return out


# -- RPR001: host sync in hot paths ----------------------------------------------

#: functions whose bodies are per-iteration hot paths in THIS codebase:
#: the engine loop and its line-search helpers, the per-iteration
#: diagnostics extraction, the telemetry memory poll, and the serving
#: rowwise solve wrapper.
HOT_SCOPE_NAMES = frozenset({
    "fit_loop", "_fit_loop", "initial_step", "host_backtrack",
    "diagnostics", "device_memory_stats", "rowwise_transform",
})

#: calls that force (or, inside jit, fail on) a device round-trip.
#: explicit `jax.device_get` in HOST loops is deliberately absent — a
#: single batched device_get is the sanctioned fix for these findings;
#: it is only flagged inside jitted bodies (where it is a trace error).
_SYNC_CALLS = {
    "np.asarray": "np.asarray",
    "numpy.asarray": "np.asarray",
    "np.array": "np.array",
    "numpy.array": "np.array",
    "float": "float()",
    "int": "int()",
}


def _device_tainted(fns) -> set[str]:
    """Names plausibly bound to device arrays in the given functions:
    any assignment whose RHS mentions jnp./jax., and tuple-unpacks of a
    call result (step/energy functions return device tuples).  Keeps
    `float(max_iters)`-style host config normalization out of RPR001."""
    tainted: set[str] = set()
    for fn in fns:
        for node in ast.walk(fn):
            if isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                 ast.GeneratorExp)):
                # comprehension over device state stashed on self
                # (e.g. `float(v) for k, v in self._diag.items()`)
                for gen in node.generators:
                    it_src = ast.unparse(gen.iter)
                    if "device_get" in it_src:
                        continue   # explicit transfer: values are host
                    if "self." in it_src or "jnp." in it_src \
                            or "jax." in it_src:
                        tainted.update(_assigned_names(gen.target))
                continue
            if not isinstance(node, ast.Assign):
                continue
            seg = ast.unparse(node.value)
            if "device_get" in seg:
                # names coming off an explicit device_get are HOST
                # values — float()/int() of them is the sanctioned fix
                continue
            from_jax = "jnp." in seg or "jax." in seg
            unpack = (isinstance(node.value, ast.Call)
                      and any(isinstance(t, (ast.Tuple, ast.List))
                              for t in node.targets))
            if from_jax or unpack:
                for t in node.targets:
                    tainted.update(_assigned_names(t))
    return tainted


def rule_rpr001(tree: ast.Module, path: str, src: str) -> list[Finding]:
    findings = []
    jitted = {id(fn) for fn, _ in _jitted_functions(tree)}

    def in_hot(name: str, fn: ast.AST, parents) -> bool:
        last = name.rsplit(".", 1)[-1]
        if last in HOT_SCOPE_NAMES or id(fn) in jitted:
            return True
        return any(p.name in HOT_SCOPE_NAMES or id(p) in jitted
                   for p in parents
                   if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)))

    def is_jitted(fn, parents) -> bool:
        return id(fn) in jitted or any(id(p) in jitted for p in parents)

    for scope, fn, parents in _walk_scopes(tree):
        if not in_hot(scope, fn, parents):
            continue
        tainted = _device_tainted([fn] + list(parents))
        # nested defs get their own scope entry — don't double-report
        nested = {id(n) for _, f, _ in _walk_scopes(fn) for n in ast.walk(f)}
        for node in ast.walk(fn):
            if id(node) in nested or not isinstance(node, ast.Call):
                continue
            # .item() on anything
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                findings.append(Finding(
                    "RPR001", path, node.lineno, node.col_offset, scope,
                    "`.item()` in hot scope: blocking device->host sync "
                    "per call (batch transfers with one jax.device_get)"))
                continue
            q = qualname(node.func)
            if q == "jax.devices":
                findings.append(Finding(
                    "RPR001", path, node.lineno, node.col_offset, scope,
                    "`jax.devices()` in hot scope: device enumeration "
                    "per call — hoist/cache the device handle"))
                continue
            if q == "jax.device_get" and is_jitted(fn, parents):
                findings.append(Finding(
                    "RPR001", path, node.lineno, node.col_offset, scope,
                    "`jax.device_get` inside a jitted body: trace "
                    "error — move the transfer outside jit"))
                continue
            label = _SYNC_CALLS.get(q)
            if label is None or not node.args:
                continue
            a = node.args[0]
            arg_src = ast.unparse(a)
            device_arg = ("jnp." in arg_src or "jax." in arg_src
                          or "self." in arg_src
                          or (isinstance(a, ast.Name) and a.id in tainted))
            if not device_arg:
                continue
            findings.append(Finding(
                "RPR001", path, node.lineno, node.col_offset, scope,
                f"`{label}` of device value in hot scope: implicit "
                f"device->host sync per call (inside jit this is a "
                f"trace error; batch transfers with one "
                f"jax.device_get)"))
    return findings


# -- RPR002: PRNG key reuse ------------------------------------------------------

#: jax.random functions that DERIVE keys rather than consume them
_KEY_DERIVERS = frozenset({"split", "fold_in", "PRNGKey", "key", "key_data",
                           "wrap_key_data", "clone"})


def _assigned_names(target: ast.AST):
    for node in ast.walk(target):
        if isinstance(node, ast.Name):
            yield node.id


def rule_rpr002(tree: ast.Module, path: str, src: str) -> list[Finding]:
    findings = []
    for scope, fn, _ in _walk_scopes(tree):
        # events in source order: ("assign"|"use", name, node)
        events: list[tuple[str, str, ast.AST]] = []
        nested = {id(n) for _, f, _ in _walk_scopes(fn) for n in ast.walk(f)}
        for node in ast.walk(fn):
            if id(node) in nested or node is fn:
                continue
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    for name in _assigned_names(t):
                        events.append(("assign", name, node))
            elif isinstance(node, ast.Call):
                q = qualname(node.func)
                if not q.startswith(("jax.random.", "random.")):
                    continue
                fn_name = q.rsplit(".", 1)[-1]
                if fn_name in _KEY_DERIVERS:
                    continue
                for a in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(a, ast.Name):
                        events.append(("use", a.id, node))
                        break   # first name arg is the key by convention
        events.sort(key=lambda e: (e[2].lineno, e[2].col_offset))
        last: dict[str, str] = {}
        for kind, name, node in events:
            if kind == "use" and last.get(name) == "use":
                findings.append(Finding(
                    "RPR002", path, node.lineno, node.col_offset, scope,
                    f"PRNG key `{name}` consumed by a second jax.random "
                    f"draw without split/fold_in: correlated samples"))
            last[name] = kind
    return findings


# -- RPR003: jit retrace hazards -------------------------------------------------


def _module_mutable_config(tree: ast.Module) -> set[str]:
    """Module-level names bound to dict/list/set literals (mutable config
    a jitted closure must not capture — mutation won't retrigger a
    trace, so the compiled program silently goes stale)."""
    out = set()
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(
                node.value, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                             ast.ListComp, ast.SetComp)):
            for t in node.targets:
                if isinstance(t, ast.Name) and not t.id.isupper():
                    # UPPER_CASE module constants are treated as frozen
                    out.add(t.id)
    return out


def rule_rpr003(tree: ast.Module, path: str, src: str) -> list[Finding]:
    findings = []
    mutable_cfg = _module_mutable_config(tree)
    for fn, static in _jitted_functions(tree):
        args = fn.args
        all_args = args.posonlyargs + args.args + args.kwonlyargs
        defaults = dict(zip([a.arg for a in reversed(args.args)],
                            list(reversed(args.defaults))))
        kw_defaults = {a.arg: d for a, d in
                       zip(args.kwonlyargs, args.kw_defaults)
                       if d is not None}
        defaults.update(kw_defaults)
        for a in all_args:
            if a.arg in static:
                continue
            ann = a.annotation
            ann_name = qualname(ann) if ann is not None else ""
            d = defaults.get(a.arg)
            hashable_py = (ann_name in ("str", "bool")
                           or (isinstance(d, ast.Constant)
                               and isinstance(d.value, (str, bool))))
            if hashable_py:
                findings.append(Finding(
                    "RPR003", path, a.lineno, a.col_offset, fn.name,
                    f"jitted fn param `{a.arg}` takes a Python str/bool "
                    f"but is not in static_argnames: bool retraces per "
                    f"value, str is a trace error"))
            if isinstance(d, (ast.Dict, ast.List, ast.Set)):
                findings.append(Finding(
                    "RPR003", path, a.lineno, a.col_offset, fn.name,
                    f"jitted fn param `{a.arg}` has a mutable default: "
                    f"shared across traces and invisible to the jit "
                    f"cache key"))
        local = {n for stmt in ast.walk(fn) for n in (
            _assigned_names(stmt.targets[0])
            if isinstance(stmt, ast.Assign) else ())}
        local |= {a.arg for a in all_args}
        for node in ast.walk(fn):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in mutable_cfg and node.id not in local):
                findings.append(Finding(
                    "RPR003", path, node.lineno, node.col_offset, fn.name,
                    f"jitted fn closes over module-level mutable "
                    f"`{node.id}`: mutation will not retrigger tracing "
                    f"(freeze it or pass it as an argument)"))
    return findings


# -- RPR004: Pallas tile constraints ---------------------------------------------

#: minimum legal TPU sublane multiple (f32; bf16 needs 16 — 8 catches
#: every layout because 16 % 8 == 0 and a non-multiple-of-8 literal is
#: illegal for both)
_SUBLANE = 8


def rule_rpr004(tree: ast.Module, path: str, src: str) -> list[Finding]:
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        q = qualname(node.func)
        if not q.endswith("BlockSpec"):
            continue
        if node.args and isinstance(node.args[0], ast.Tuple):
            for i, el in enumerate(node.args[0].elts):
                # literal 1 = scalar/broadcast block (e.g. the (1, 1)
                # SMEM-style accumulator outputs): always legal
                if (isinstance(el, ast.Constant)
                        and isinstance(el.value, int)
                        and el.value != 1
                        and el.value % _SUBLANE != 0):
                    findings.append(Finding(
                        "RPR004", path, el.lineno, el.col_offset,
                        "<module>",
                        f"BlockSpec dim {i} literal {el.value} is not a "
                        f"multiple of the sublane tile ({_SUBLANE} rows "
                        f"f32 / 16 bf16): Mosaic pads or rejects the "
                        f"tile (use kernels.ops.legal_tile)"))
        for kw in node.keywords:
            if (kw.arg == "memory_space"
                    and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, str)):
                findings.append(Finding(
                    "RPR004", path, kw.value.lineno, kw.value.col_offset,
                    "<module>",
                    f"BlockSpec memory_space passed as the raw string "
                    f"{kw.value.value!r}: use the version-shimmed "
                    f"pltpu/pl symbols (kernels.sparse_attractive._space)"))
    return findings


# -- RPR005: bf16 reductions without an f32 accumulator --------------------------

_REDUCERS = ("jnp.sum", "jnp.mean", "jnp.prod", "jnp.dot", "jnp.matmul",
             "jnp.einsum", "jnp.vdot")


def rule_rpr005(tree: ast.Module, path: str, src: str) -> list[Finding]:
    findings = []
    for scope, fn, _ in _walk_scopes(tree):
        tainted: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                seg = ast.unparse(node.value)
                names = [n for t in node.targets
                         for n in _assigned_names(t)]
                if "bfloat16" in seg or "bf16" in seg:
                    tainted.update(names)
                elif "float32" in seg:
                    tainted.difference_update(names)
        if not tainted:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            q = qualname(node.func)
            arg_names = {a.id for a in node.args
                         if isinstance(a, ast.Name)}
            if not (arg_names & tainted):
                continue
            kwargs = {kw.arg for kw in node.keywords}
            if q in _REDUCERS and "dtype" not in kwargs \
                    and "preferred_element_type" not in kwargs:
                findings.append(Finding(
                    "RPR005", path, node.lineno, node.col_offset, scope,
                    f"`{q}` reduces a bf16-stored value without "
                    f"dtype=/preferred_element_type=jnp.float32: "
                    f"accumulates in bf16 (upcast after the gather, "
                    f"accumulate in f32)"))
            elif q.endswith("dot_general") \
                    and "preferred_element_type" not in kwargs:
                findings.append(Finding(
                    "RPR005", path, node.lineno, node.col_offset, scope,
                    "`dot_general` on a bf16-stored value without "
                    "preferred_element_type=jnp.float32: the MXU "
                    "accumulates in bf16"))
    return findings


# -- RPR006: DeprecationWarning without stacklevel=2 -----------------------------


def rule_rpr006(tree: ast.Module, path: str, src: str) -> list[Finding]:
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if qualname(node.func) not in ("warnings.warn", "warn"):
            continue
        cat = node.args[1] if len(node.args) > 1 else None
        for kw in node.keywords:
            if kw.arg == "category":
                cat = kw.value
        if cat is None or qualname(cat) != "DeprecationWarning":
            continue
        level = None
        for kw in node.keywords:
            if kw.arg == "stacklevel":
                level = kw.value
        if level is None or (isinstance(level, ast.Constant)
                             and isinstance(level.value, int)
                             and level.value < 2):
            findings.append(Finding(
                "RPR006", path, node.lineno, node.col_offset, "<module>",
                "DeprecationWarning without stacklevel=2: the warning "
                "points at the shim, not at the caller to migrate"))
    return findings


# -- RPR007: span() not used as a context manager --------------------------------


def rule_rpr007(tree: ast.Module, path: str, src: str) -> list[Finding]:
    findings = []
    for scope, fn, _ in _walk_scopes(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Expr):
                continue
            call = node.value
            if not isinstance(call, ast.Call):
                continue
            q = qualname(call.func)
            if q == "span" or q.endswith(".span"):
                findings.append(Finding(
                    "RPR007", path, call.lineno, call.col_offset, scope,
                    "`span(...)` called but discarded: nothing is timed "
                    "— use `with span(...):` around the block"))
    return findings


ALL_RULES: dict[str, Callable] = {
    "RPR001": rule_rpr001,
    "RPR002": rule_rpr002,
    "RPR003": rule_rpr003,
    "RPR004": rule_rpr004,
    "RPR005": rule_rpr005,
    "RPR006": rule_rpr006,
    "RPR007": rule_rpr007,
}
