"""Device mesh helpers.

Two axes live here:

* ``SHARD_AXIS`` ("shards") — the pod tier's one-axis mesh (backend_pod).
* ``SLOT_AXIS`` ("slots") — the cluster mesh data plane's axis: a single
  HLL bank whose rows (slot-range sketches) are sharded across the mesh
  via ``NamedSharding(mesh, PartitionSpec("slots"))`` so N logical shards
  share one device-resident program (``ShardedBank``).

``get_mesh`` is the CACHED constructor: topology-change storms
(node_up/node_down scans re-resolving the same device set) must not mint
fresh ``Mesh`` objects — a new Mesh is a new jit cache key, and every
shard_map/jit against it re-traces. The cache is invalidated only when
the resolved device set actually changes; ``mesh_cache_stats`` exposes
build/hit counters so tests can pin the no-rebuild contract.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shards"
SLOT_AXIS = "slots"

# Lock discipline (graftlint Tier C): every name in this table is only
# touched under the named lock.
GUARDED_BY = {
    "_MESH_CACHE": "_CACHE_LOCK",
    "_CACHE_STATS": "_CACHE_LOCK",
}

_CACHE_LOCK = threading.Lock()
# (num_devices, axis, device ids) -> Mesh
_MESH_CACHE: Dict[Tuple[int, str, Tuple[int, ...]], Mesh] = {}
_CACHE_STATS = {"builds": 0, "hits": 0, "invalidations": 0}


def build_mesh(num_devices: int = 0, axis: str = SHARD_AXIS) -> Mesh:
    """1-D mesh over the first num_devices devices (0 = all)."""
    devs = jax.devices()
    if num_devices:
        devs = devs[:num_devices]
    # graftlint: allow-sync(host metadata: jax.devices() is a python list of device handles, not a device array)
    return Mesh(np.array(devs), (axis,))


def get_mesh(num_devices: int = 0, axis: str = SHARD_AXIS) -> Mesh:
    """Cached ``build_mesh``: returns the SAME Mesh object for the same
    resolved device set, so reshard/on_change paths hitting this every
    scan reuse every jit/shard_map cache entry. Invalidated (and rebuilt)
    only when the device set itself changed (device loss/gain)."""
    devs = jax.devices()
    if num_devices:
        devs = devs[:num_devices]
    key = (len(devs), axis, tuple(d.id for d in devs))
    with _CACHE_LOCK:
        mesh = _MESH_CACHE.get(key)
        if mesh is not None:
            _CACHE_STATS["hits"] += 1
            return mesh
        # Same (count, axis) but a different device set: the old entry is
        # stale (a device was lost/replaced) — drop it before rebuilding.
        stale = [k for k in _MESH_CACHE
                 if k[0] == key[0] and k[1] == key[1]]
        for k in stale:
            _MESH_CACHE.pop(k, None)
            _CACHE_STATS["invalidations"] += 1
    mesh = build_mesh(num_devices, axis)
    with _CACHE_LOCK:
        _MESH_CACHE[key] = mesh
        _CACHE_STATS["builds"] += 1
    return mesh


def mesh_cache_stats() -> Dict[str, int]:
    with _CACHE_LOCK:
        return dict(_CACHE_STATS)


def reset_mesh_cache() -> None:
    """Test hook: drop every cached mesh and zero the counters."""
    with _CACHE_LOCK:
        _MESH_CACHE.clear()
        for k in _CACHE_STATS:
            _CACHE_STATS[k] = 0


def bank_sharding(mesh: Mesh, axis: str = SHARD_AXIS) -> NamedSharding:
    """[S, m] sketch bank: rows sharded across devices, registers local."""
    return NamedSharding(mesh, P(axis, None))


def slot_sharding(mesh: Mesh) -> NamedSharding:
    """The mesh data plane's bank placement: slot-range rows across the
    ``SLOT_AXIS`` mesh, register lanes local to each device."""
    return NamedSharding(mesh, P(SLOT_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


class ShardedBank:
    """Placement + row-geometry bookkeeping for the mesh data plane's
    single HLL bank.

    The bank itself stays a plain ``[S, m] int32`` jax array owned by the
    TpuBackend (every existing kernel keeps working, on CPU CI and TPU
    alike); this object carries the mesh, the sharding, and the logical
    shard -> preferred row-block map that keeps a shard's sketches
    device-local so the collective merge's pmax does the cross-shard hop
    instead of an XLA-inserted gather.

    Row blocks are a PLACEMENT HINT, not a correctness domain: when a
    shard's preferred block fills, rows spill to any free row (the
    collectives mask by row index, never by block)."""

    def __init__(self, mesh: Mesh, capacity: int, num_shards: int):
        self.mesh = mesh
        self.num_shards = max(int(num_shards), 1)
        self.capacity = self.round_capacity(capacity)
        self.sharding = slot_sharding(mesh)

    @property
    def ndev(self) -> int:
        return int(self.mesh.devices.size)

    def round_capacity(self, capacity: int) -> int:
        """Row count must divide evenly across mesh devices."""
        ndev = int(self.mesh.devices.size)
        if capacity % ndev:
            capacity += ndev - capacity % ndev
        return capacity

    def make(self, m: int):
        """A zeroed [capacity, m] int32 bank, created sharded: each device
        allocates only its own rows (engine.hll_bank_grow_mesh grows it
        the same way)."""
        return jnp.zeros((self.capacity, m), jnp.int32, device=self.sharding)

    def replicate(self, arr):
        """Commit an operand (wire/table/rows) replicated across the mesh
        so it can feed a jit together with the sharded bank (mixed
        committed-device inputs are a jit error)."""
        return jax.device_put(arr, replicated(self.mesh))

    def block(self, shard_id: int, capacity: Optional[int] = None
              ) -> Tuple[int, int]:
        """Preferred [lo, hi) row range for a logical shard's sketches."""
        cap = self.capacity if capacity is None else capacity
        width = max(cap // self.num_shards, 1)
        lo = min(shard_id * width, cap)
        hi = cap if shard_id == self.num_shards - 1 else min(lo + width, cap)
        return lo, hi
