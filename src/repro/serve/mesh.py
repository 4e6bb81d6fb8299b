"""Flow meshes: how batch streaming spreads flows over devices.

The streaming scheduler splits each micro-batch's flow axis over the
mesh's data-parallel axes (``"pod"`` and ``"data"``, whichever the mesh
has); every other dim (partition, window, packet fields) stays local.
Like every mesh constructor, :func:`make_flow_mesh` touches device state
only when called, never on import.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, PartitionSpec

#: Mesh axes the flow axis may ride, outermost first.
FLOW_AXES = ("pod", "data")


def make_flow_mesh(n_data: int | None = None):
    """1-D ("data",) mesh for flow-batch sharding (streaming engine).

    ``n_data`` defaults to every visible device — the serving topology
    where one host fans flow micro-batches out across its accelerators.
    """
    n = len(jax.devices()) if n_data is None else n_data
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))


def _flow_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in FLOW_AXES if a in mesh.axis_names)


def flow_batch_spec(mesh) -> PartitionSpec:
    """PartitionSpec for a flow-batch tensor (leading dim = flows).

    Used as the ``shard_map`` in/out spec for the partition walk."""
    axes = _flow_axes(mesh)
    if not axes:
        raise ValueError(
            f"mesh axes {mesh.axis_names} have no data-parallel axis "
            f"(need one of {FLOW_AXES})")
    return PartitionSpec(axes)


def flow_batch_devices(mesh) -> int:
    """How many ways :func:`flow_batch_spec` splits the flow axis."""
    return int(np.prod([mesh.shape[a] for a in _flow_axes(mesh)]))
