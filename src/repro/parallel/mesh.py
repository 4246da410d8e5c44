"""Device meshes the engines shard over.

Every mesh here has ``Auto`` axes.  JAX 0.9's ``jax.make_mesh`` hands out
``Explicit`` axes by default, under which arrays carry their sharding in
their type and gathers over a sharded fleet axis refuse to trace; the
fleet engines and the gateway shard with ``shard_map`` / placed state and
let the compiler propagate the rest.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes (over ``devices`` if given)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_fleet_mesh(devices=None, axis: str = "data"):
    """1-D mesh over ``devices`` (default: every local device) — the
    device fleet sharded along ``axis`` (``simulate_sharded_stream``,
    ``GatewayCore(mesh=)``)."""
    devices = jax.local_devices() if devices is None else list(devices)
    return make_mesh((len(devices),), (axis,), devices=devices)


def auto_axes(mesh):
    """``mesh`` with every axis ``Auto`` (see the module docstring)."""
    from jax.sharding import Mesh

    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def fleet_mesh(mesh, N: int, device_axis: str):
    """The mesh a sharded fleet engine runs on: ``mesh`` with ``Auto``
    axes, once N is checked to split evenly over ``device_axis``."""
    n_shards = mesh.shape[device_axis]
    if N % n_shards:
        raise ValueError(
            f"fleet size N={N} must be a multiple of the {device_axis!r} "
            f"axis shard count ({n_shards})")
    return auto_axes(mesh)
