"""Entry-point contracts that hold without a chip: `chip_smoke.py` refuses
to report a result off the TPU or outside a checkout, and the compile
cache lands where `repro.compile_cache` promises."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro import compile_cache
from repro.launch.mesh import auto_axes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu_or_checkout(tmp_path, where):
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)          # no result line of any kind


def test_compile_cache_respects_env(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV, "/some/where")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_inside_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()     # never committed


def test_auto_axes_converts_explicit_mesh():
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    assert all(t == AxisType.Explicit for t in mesh.axis_types)
    auto = auto_axes(mesh)
    assert all(t == AxisType.Auto for t in auto.axis_types)
    assert auto.axis_names == mesh.axis_names
    assert (auto.devices == mesh.devices).all()
    assert auto_axes(auto) is auto
