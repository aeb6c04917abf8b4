"""The mesh/shard_map substrate, on the installed JAX (0.9).

Everything that builds a mesh or enters SPMD routes through here, so the
choices made once — ``Auto`` axis types, caller-ordered device arrays,
``shard_map`` without varying-manual-axes checking — hold repo-wide.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(
    shape: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Sequence[Any] | None = None,
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types."""

    return jax.make_mesh(
        tuple(shape),
        tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(shape),
        devices=devices,
    )


def mesh_from_devices(device_array, axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """Build a ``Mesh`` from an already-arranged device array, preserving the
    caller's device order exactly (``make_mesh`` may reorder for physical
    topology, which would break group-rank ↔ device contracts)."""

    return jax.sharding.Mesh(
        device_array,
        tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names),
    )


def abstract_mesh(shape: Sequence[int], axis_names: Sequence[str]) -> AbstractMesh:
    """A device-free ``AbstractMesh`` of ``shape`` over ``axis_names``."""

    return AbstractMesh(tuple(shape), tuple(axis_names))


def shard_map(
    fn: Callable,
    *,
    mesh: jax.sharding.Mesh,
    in_specs: Any,
    out_specs: Any,
) -> Callable:
    """``jax.shard_map`` without varying-manual-axes checking."""

    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
