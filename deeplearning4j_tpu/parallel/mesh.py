"""Device-mesh construction and sharding helpers.

Replaces the reference's device topology plumbing (`AffinityManager`,
`MeshOrganizer` node-tree in `nd4j-parameter-server-node`): on TPU the
topology is the XLA device mesh, and "mesh formation" is just naming axes.
Axis convention (scaling-book style): `data` (DP), `model` (TP), `pipe`
(PP), `seq` (SP/context).  Multi-host control plane = `jax.distributed`
(the Aeron mesh's control role), not anything here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes, e.g. {'data': 4, 'model': 2}.  Axis order follows
    insertion order; sizes must multiply to the device count used."""

    axes: Dict[str, int]

    def total(self) -> int:
        n = 1
        for v in self.axes.values():
            n *= v
        return n


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh over `devices` (default: all).  With no axes given,
    a pure data-parallel mesh over every device — the ParallelWrapper
    default of one worker per device."""
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"data": len(devices)}
    spec = MeshSpec(dict(axes))
    if spec.total() != len(devices):
        raise ValueError(
            f"Mesh axes {axes} require {spec.total()} devices, "
            f"have {len(devices)}")
    arr = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def data_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Sharding for a batch: leading (batch) dim split over `axis`."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
