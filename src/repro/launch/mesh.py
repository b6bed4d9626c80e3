"""Production meshes (assignment spec) and the shared shard_map helpers.

`make_production_mesh` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  Only
launch/dryrun.py forces the 512 host devices.

Every mesh the repo builds has Auto axes: `jax.make_mesh` without
`axis_types` builds Explicit axes, under which a contraction over a
sharded dimension outside `shard_map` (the engine's line-search dot
products) is a sharding-type error.  `auto_axes` converts a caller's
mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

shard_map = jax.shard_map


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """`shard_map` with replication checking disabled — `pallas_call` has
    no replication rule, so kernel-bearing bodies (sparse/sharding.py with
    the local-rows ELL kernel) cannot pass the check.  Only kernel-bearing
    bodies should use this — plain jnp bodies keep the default checking."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def linear_row_index(row_axes: tuple[str, ...]):
    """Linear (row-major) block index of this device across `row_axes`,
    inside a shard_map body — the mapping every row-sharded layout
    (embed/distributed.py, sparse/sharding.py) uses to find its global
    row offset, matching the P(row_axes, ...) shard order."""
    import jax.numpy as jnp

    idx = jnp.asarray(0, jnp.int32)
    for ax in row_axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def axis_types_kwargs(n_axes: int) -> dict:
    """`{"axis_types": (Auto,) * n}`: the kwarg for an Auto-axes mesh."""
    return {"axis_types": (AxisType.Auto,) * n_axes}


def auto_axes(mesh: Mesh) -> Mesh:
    """`mesh` with every axis Auto (same devices, same names)."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                **axis_types_kwargs(len(mesh.axis_names)))


def make_abstract_mesh(shape: tuple[int, ...],
                       names: tuple[str, ...]) -> AbstractMesh:
    return AbstractMesh(shape, names, **axis_types_kwargs(len(names)))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **axis_types_kwargs(len(axes)))


def make_host_mesh(model_axis: int = 1) -> Mesh:
    """Whatever devices exist, as (data, model) — used by tests/examples."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         **axis_types_kwargs(2))


def n_chips(mesh: Mesh) -> int:
    return mesh.devices.size
