"""Mesh context threaded through model apply functions.

Carries the physical mesh plus the role of each axis so modules that need
explicit collectives (MoE all_to_all dispatch) can name them. ``dp_axes``
shard the batch (("pod","data") multi-pod, ("data",) single-pod), ``fsdp``
is the axis params are fully-sharded over, ``tp`` shards
heads / d_ff / experts / vocab.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    mesh: Mesh
    dp_axes: Tuple[str, ...] = ("data",)
    fsdp_axis: str = "data"
    tp_axis: str = "model"

    @property
    def dp_size(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.dp_axes)

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


def single_device_ctx(device=None) -> MeshCtx:
    """1-device mesh with production axis names — smoke tests run the exact
    same (shard_map-containing) code paths on CPU. ``device`` picks the
    chip (None: the first of ``jax.devices()``), so several single-chip
    sessions of one process can each own a different chip."""
    device = jax.devices()[0] if device is None else device
    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"))
    return MeshCtx(mesh=mesh, dp_axes=("data",), fsdp_axis="data",
                   tp_axis="model")
