"""Process-global device mesh for the serving path.

The reference fans searches out across nodes with per-shard goroutines
(``index.go:1928``); within one multi-chip TPU host the equivalent is a
single SPMD program over a ``jax.sharding.Mesh``. This module owns the
process-wide default mesh: when more than one device is visible (a v5e-8,
or the 8-device virtual CPU platform used in tests), HBM-resident stores
shard their corpus rows across it and searches run via ``shard_map`` with
ICI collectives; with one device everything stays single-device.

Kill switch: ``WEAVIATE_TPU_MESH=off`` forces single-device mode.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Optional

from jax.sharding import Mesh

_lock = threading.Lock()
_mesh: Optional[Mesh] = None
_resolved = False


def default_mesh() -> Optional[Mesh]:
    """The process-wide mesh, or None when only one device is available.

    Resolved lazily on first use (so tests can force the CPU platform
    first) and cached; ``set_mesh`` overrides.
    """
    global _mesh, _resolved
    with _lock:
        if _resolved:
            return _mesh
        if os.environ.get("WEAVIATE_TPU_MESH", "").lower() in ("off", "0", "false"):
            _mesh, _resolved = None, True
            return None
        import jax

        from weaviate_tpu.parallel.mesh import make_mesh

        devices = jax.devices()
        if len(devices) > 1:
            _mesh = make_mesh(len(devices))
        else:
            _mesh = None
        _resolved = True
        return _mesh


def set_mesh(mesh: Optional[Mesh]) -> None:
    """Override the default mesh (tests / explicit deployment config)."""
    global _mesh, _resolved
    with _lock:
        _mesh = mesh
        _resolved = True


def reset() -> None:
    """Forget the cached resolution (test helper)."""
    global _mesh, _resolved
    with _lock:
        _mesh = None
        _resolved = False


def device_report() -> dict:
    """What this process runs on, for the boot line and ``/v1/nodes``:
    platform, device kind and count, and the bytes each device holds —
    from ``memory_stats()`` where the backend reports it, else summed
    from the shards of ``jax.live_arrays()`` (the CPU backend)."""
    import jax

    devices = jax.devices()
    stats = [d.memory_stats() for d in devices]
    if all(st and "bytes_in_use" in st for st in stats):
        in_use = [int(st["bytes_in_use"]) for st in stats]
    else:
        held = {d.id: 0 for d in devices}
        for arr in jax.live_arrays():
            per_device = (math.prod(arr.sharding.shard_shape(arr.shape))
                          * arr.dtype.itemsize)
            for d in arr.sharding.device_set:
                held[d.id] += per_device
        in_use = [held[d.id] for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "bytes_in_use": in_use,
    }
