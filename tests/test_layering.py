"""The package layer map (docs/architecture.md): imports point downward,
`api -> embed -> {core, sparse, kernels} -> {obs, ckpt}`, with `serve` on
top of `api`.  Every `import` in a package's source counts, function-level
(deferred) imports included: a deferred import still makes the lower
layer depend on the upper one."""
from __future__ import annotations

import ast
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"

_ABOVE_EMBED = ("repro.embed", "repro.api", "repro.serve")

#: package -> the packages above it that it must never import
FORBIDDEN = {
    "obs": _ABOVE_EMBED,
    "ckpt": _ABOVE_EMBED,
    "kernels": _ABOVE_EMBED,
    "core": _ABOVE_EMBED,
    "sparse": _ABOVE_EMBED,
    "embed": ("repro.api", "repro.serve"),
    "api": ("repro.serve",),
}


def _imported_modules(path: pathlib.Path) -> list[tuple[int, str]]:
    """(line, absolute module name) for every import in one source file;
    `from x import y` yields both `x` and `x.y` (y may be a submodule)."""
    # the package a relative import is resolved against (for __init__.py
    # too: its package is its own directory)
    package = path.relative_to(SRC.parent).parts[:-1]
    out = []
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out += [(node.lineno, a.name) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package[:len(package) - node.level + 1]
                mod = ".".join(base + ((node.module,) if node.module else ()))
            else:
                mod = node.module
            out.append((node.lineno, mod))
            out += [(node.lineno, f"{mod}.{a.name}") for a in node.names]
    return out


@pytest.mark.parametrize("package", sorted(FORBIDDEN))
def test_package_imports_point_downward(package):
    forbidden = FORBIDDEN[package]
    files = sorted((SRC / package).rglob("*.py"))
    assert files, f"no sources under {SRC / package}"
    bad = [f"{p.relative_to(SRC)}:{line}: {mod}"
           for p in files for line, mod in _imported_modules(p)
           if any(mod == f or mod.startswith(f + ".") for f in forbidden)]
    assert not bad, (f"repro.{package} imports from a layer above it:\n"
                     + "\n".join(bad))
