"""Device-mesh management — the communicator registry of the TPU world.

Reference analog: NCCLCommContext, a global map (ring_id, device)→communicator
(platform/collective_helper.h:67).  On TPU, "rings" are named mesh axes over
the chip grid: collectives ride ICI along an axis; there are no streams or
communicator handles to manage (XLA schedules async collectives).  This module
owns the process-global Mesh and the ring_id→axis-name mapping so the
reference's Group/ring APIs can be reproduced on top.

Canonical axis names: 'dp' (data), 'mp' (tensor/model), 'pp' (pipeline),
'sp' (sequence/context), 'ep' (expert).  No exchange rides 'ep' yet:
nn.SparseExpertShare is told which experts it holds (`experts_held` = start
and count of the router's published width) and computes their part alone —
in both expert models that wait for the axis, text.models.KimiLinearModel
(8 of 256 experts a chip, EP32) and text.models.Lfm2MoeModel (8 of 32,
EP4).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_GLOBAL_MESH: Optional[Mesh] = None


def init_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Create + install the global mesh, e.g. init_mesh({'dp': 4, 'mp': 2})."""
    global _GLOBAL_MESH
    devs = np.array(devices if devices is not None else jax.devices())
    shape = tuple(axes.values())
    total = int(np.prod(shape))
    if total > devs.size:
        raise ValueError(f"mesh {axes} needs {total} devices, have {devs.size}")
    mesh = Mesh(devs[:total].reshape(shape), tuple(axes.keys()))
    _GLOBAL_MESH = mesh
    return mesh


def get_mesh() -> Mesh:
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        devs = np.array(jax.devices())
        _GLOBAL_MESH = Mesh(devs, ("dp",))
    return _GLOBAL_MESH


def set_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def mesh_axis_size(name: str) -> int:
    mesh = get_mesh()
    return mesh.shape.get(name, 1)


def spec(*names) -> PartitionSpec:
    return PartitionSpec(*names)


def sharding(*names) -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec(*names))


def replicated_sharding() -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec())


def shard_array(x, *axis_names):
    """Place a host array onto the mesh with dim i sharded over axis_names[i]
    (None entries = replicated dims)."""
    return jax.device_put(x, NamedSharding(get_mesh(), PartitionSpec(*axis_names)))
