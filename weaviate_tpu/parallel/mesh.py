"""Device mesh construction.

The reference scales reads by fanning out per-shard goroutines across nodes
(``index.go:1928``) over HTTP. The TPU-native equivalent is a
``jax.sharding.Mesh`` over ICI: shards are corpus partitions laid out along a
single ``shard`` mesh axis; collectives (all_gather of per-device top-k)
replace the clusterapi scatter-gather.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

SHARD_AXIS = "shard"


def mesh_size(mesh: Mesh) -> int:
    """Device count along all mesh axes (the shard count)."""
    return int(np.prod(mesh.devices.shape))


def shard_of(ids, capacity: int, n_shards: int):
    """Block-shard membership for row ids under the store's row-block
    layout: shard s owns rows [s*L, (s+1)*L) with L = capacity //
    n_shards. Growth in mesh mode multiplies capacity by an integer
    factor (see DeviceVectorStore.ensure_capacity), so membership only
    ever COARSENS — an intra-shard graph edge stays intra-shard across
    every grow."""
    return np.asarray(ids) // (capacity // n_shards)


def make_mesh(n_devices: Optional[int] = None, axis: str = SHARD_AXIS) -> Mesh:
    """Build a 1-D mesh over the first ``n_devices`` devices of the default
    platform (all of them when ``None``). Raises when the platform has
    fewer: a mesh never stands on devices other than the ones asked for.
    """
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices; platform "
                f"{devices[0].platform!r} has {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))
