"""Production meshes for the TPU v5e target.

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips, where the 'pod' axis carries pure data parallelism
(DCN-attached; only gradient all-reduce crosses pods).

`make_production_mesh` is a FUNCTION so importing this module never touches
jax device state — the dry-run sets `--xla_force_host_platform_device_count`
before any jax initialization and only then builds the mesh.

Every mesh here has *Auto* axes: the programs in this repo state their
layouts as PartitionSpecs (shard_map in/out specs, jit in_shardings,
sharding constraints) and let the partitioner place everything else.
`jax.make_mesh` now defaults to Explicit axes, under which a plain gather
of a sharded array raises `ShardingTypeError`; `auto_axes` converts a
caller's mesh at the boundary.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

# TPU v5e hardware constants (roofline terms, EXPERIMENTS.md §Roofline).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """`jax.make_mesh` with Auto axes (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names, every axis Auto."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke paths (axis names match production)."""
    return make_mesh((1, 1), ("data", "model"))


def make_data_mesh():
    """All local devices on the data axis of a ("data", "model") mesh —
    the default of the sharded fit tiers."""
    return make_mesh((jax.device_count(), 1), ("data", "model"))


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size)
