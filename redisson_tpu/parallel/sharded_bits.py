"""Sharded bit arrays: ONE logical bit array spanning the device mesh.

The last SURVEY §5 parallelism capability (VERDICT r4 missing #1): the
reference executes BITOP/bloom ops wherever the data lives and fans in with
SlotCallback (`RedissonBitSet.java:81-118`,
`command/CommandAsyncService.java:128-164`); the TPU-native redesign shards
the bit axis itself so a 2^32-bit filter — the check_size cap, and the
ceiling of the uint32 index math — is first-class even though no single
chip could hold it:

  * bits live unpacked (one uint8 cell per bit, same layout as the
    single-chip tier, ops/bitset.py) as an [n] array with
    NamedSharding(P('shards')) — n/D contiguous bits per device, so every
    device owns one contiguous bit range (the slot-range analogue);
  * SETBIT/GETBIT batches are replicated to all devices; inside shard_map
    each device masks the indexes landing in its range, scatters locally,
    and the gathered old-values fan in with ONE `lax.psum` over ICI (each
    bit has exactly one owner, so sum == select) — the all-reduce(or) the
    survey prescribes;
  * BITOP AND/OR/XOR between same-sharded arrays is purely local
    elementwise compute (zero communication — co-sharding IS the hashtag
    trick); BITCOUNT is a local popcount + psum, which XLA's GSPMD inserts
    automatically from the sharding;
  * bloom add/contains hash replicated (hashing is cheap, the array is the
    big thing) and reuse the same masked-scatter/psum-gather bodies over
    [N, k] double-hashed indexes.

Physical length is padded to a device multiple; callers track the logical
bit count and mask where semantics demand (NOT, set_range) so padding cells
stay zero and never leak into BITCOUNT/length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from redisson_tpu.ops import bloom
from redisson_tpu.ops.hashing import murmur3_x64_128, murmur3_x64_128_u64
from redisson_tpu.ops.u64 import U64
from redisson_tpu.parallel.mesh import SHARD_AXIS

ALLOC_GRAIN = 1024  # per-device allocation granularity (bits)


def bits_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(SHARD_AXIS))


def physical_size(nbits: int, mesh: Mesh) -> int:
    """Smallest device-divisible physical length >= nbits."""
    grain = ALLOC_GRAIN * mesh.devices.size
    return max(grain, (nbits + grain - 1) // grain * grain)


def make_bits(mesh: Mesh, nbits: int) -> jax.Array:
    """Zero bit array of physical_size(nbits) cells, bit-range sharded."""
    return jax.device_put(
        jnp.zeros((physical_size(nbits, mesh),), jnp.uint8),
        bits_sharding(mesh))


# -- scatter/gather bodies ---------------------------------------------------


def _local_mask(bits_local, idx, valid):
    """(mine, safe_local_index) for this device's contiguous bit range.

    All index math is UNSIGNED 32-bit modular distance: li = idx - start
    wraps past 2^32, and `li < n_local` selects exactly [start,
    start+n_local) for any idx up to 2^32-1 — int32 would silently wrap
    indexes >= 2^31 negative and drop the bits (review r5: a 2^32-bit
    filter is within check_size and the whole point of the sharded tier)."""
    n_local = bits_local.shape[0]
    start = lax.axis_index(SHARD_AXIS).astype(jnp.uint32) * jnp.uint32(n_local)
    li = idx.astype(jnp.uint32) - start
    mine = valid & (li < jnp.uint32(n_local))
    safe = jnp.where(mine, li, jnp.uint32(0))
    return mine, safe


def _scatter_body(bits_local, idx, valid, set_value: bool):
    """Per-device SETBIT/clear: mask my bit range, scatter locally, fan the
    pre-write values in with psum (one owner per bit => sum == select)."""
    mine, safe = _local_mask(bits_local, idx, valid)
    old_local = jnp.where(mine, bits_local[safe], 0).astype(jnp.int32)
    if set_value:
        new = bits_local.at[safe].max(mine.astype(jnp.uint8))
    else:
        new = bits_local.at[safe].min(
            jnp.where(mine, jnp.uint8(0), jnp.uint8(1)))
    return new, lax.psum(old_local, SHARD_AXIS)


def _gather_body(bits_local, idx, valid):
    mine, safe = _local_mask(bits_local, idx, valid)
    local = jnp.where(mine, bits_local[safe], 0).astype(jnp.int32)
    return lax.psum(local, SHARD_AXIS)


@functools.partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def set_bits(bits, idx, valid, mesh: Mesh):
    """SETBIT batch -> (new_bits, old_values[K] int32). One SPMD program."""
    fn = shard_map(
        functools.partial(_scatter_body, set_value=True),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P()),
        out_specs=(P(SHARD_AXIS), P()),
    )
    return fn(bits, idx, valid)


@functools.partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def clear_bits(bits, idx, valid, mesh: Mesh):
    fn = shard_map(
        functools.partial(_scatter_body, set_value=False),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P()),
        out_specs=(P(SHARD_AXIS), P()),
    )
    return fn(bits, idx, valid)


@functools.partial(jax.jit, static_argnames=("mesh",))
def get_bits(bits, idx, valid, mesh: Mesh):
    fn = shard_map(
        _gather_body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P()),
        out_specs=P(),
    )
    return fn(bits, idx, valid)


# -- whole-array ops (GSPMD partitions these from the sharding) -------------


_CARD_CHUNK = 1 << 20


@jax.jit
def cardinality_partials(bits):
    """Per-chunk int32 popcount partials (each <= 2^20, overflow-proof).

    GSPMD keeps the chunk sums local to their shards; the cross-shard
    combine happens host-side in `cardinality` with python ints, so the
    total is exact well past 2^31 set bits (a straight int32 `jnp.sum`
    wraps negative there — review r5 / ADVICE)."""
    n = bits.shape[0]
    pad = (-n) % _CARD_CHUNK
    if pad:
        bits = jnp.concatenate([bits, jnp.zeros((pad,), bits.dtype)])
    return jnp.sum(
        bits.reshape(-1, _CARD_CHUNK).astype(jnp.int32), axis=1)


def combine_partials(partials) -> int:
    """64-bit exact host-side combine of int32 popcount partials."""
    import numpy as np

    return int(np.asarray(partials, dtype=np.int64).sum())


def cardinality(bits) -> int:
    """BITCOUNT: chunked int32 partials on device, 64-bit combine on host."""
    return combine_partials(cardinality_partials(bits))


@jax.jit
def _length_parts(bits):
    """(highest set INDEX as uint32, any-set flag). The +1 happens on the
    host in python ints — adding it on device would wrap index 2^32-1 to 0
    (review r5)."""
    pos = jnp.arange(bits.shape[0], dtype=jnp.uint32)
    return (jnp.max(jnp.where(bits != 0, pos, 0)), jnp.any(bits != 0))


def length(bits) -> int:
    """Highest set bit + 1 (0 if empty) — reference lengthAsync. Correct
    up to 2^32 cells (the top index + 1 is computed host-side)."""
    idx, has = _length_parts(bits)
    return int(idx) + 1 if bool(has) else 0


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("value",))
def set_range(bits, start, last, value: bool):
    """Set [start, last] INCLUSIVE — the exclusive end of a full 2^32-bit
    range is unrepresentable in uint32 scalars (review r5), so callers pass
    end-1 and guard empty ranges themselves."""
    pos = jnp.arange(bits.shape[0], dtype=jnp.uint32)
    in_range = ((pos >= start.astype(jnp.uint32))
                & (pos <= last.astype(jnp.uint32)))
    return jnp.where(in_range, jnp.uint8(1 if value else 0), bits)


@functools.partial(jax.jit, donate_argnums=(0,))
def bitop_not(bits, last):
    """BITOP NOT over cells [0, last] inclusive; padding cells stay 0
    (inclusive bound for the same uint32-boundary reason as set_range)."""
    pos = jnp.arange(bits.shape[0], dtype=jnp.uint32)
    return jnp.where(pos <= last.astype(jnp.uint32),
                     jnp.uint8(1) - bits, bits)


@functools.partial(jax.jit, static_argnames=("op",))
def bitop(stack, op: str):
    """BITOP AND|OR|XOR over [K, n] same-sharded operands — purely local."""
    fn = {"and": jnp.bitwise_and, "or": jnp.bitwise_or,
          "xor": jnp.bitwise_xor}[op]
    acc = stack[0]
    for i in range(1, stack.shape[0]):
        acc = fn(acc, stack[i])
    return acc


# -- bloom over the sharded array -------------------------------------------


def _bloom_idx(h1, h2, valid, k: int, m: int, layout: str):
    if layout == "blocked":
        block, pos = bloom.blocked_indexes(h1, h2, k, m)
        idx = bloom.blocked_absolute(block, pos)
    else:
        idx = bloom.indexes(h1, h2, k, m)
    return jnp.where(valid[:, None], idx, 0)


def _bloom_add_body(bits_local, h1, h2, valid, k: int, m: int, layout: str):
    idx = _bloom_idx(h1, h2, valid, k, m, layout)  # replicated [N, k]
    flat = idx.reshape(-1)
    vflat = jnp.broadcast_to(valid[:, None], idx.shape).reshape(-1)
    mine, safe = _local_mask(bits_local, flat, vflat)
    old_local = jnp.where(mine, bits_local[safe], 0).astype(jnp.int32)
    new = bits_local.at[safe].max(mine.astype(jnp.uint8))
    old = lax.psum(old_local, SHARD_AXIS).reshape(idx.shape)
    return new, jnp.any(old == 0, axis=-1) & valid


def _bloom_contains_body(bits_local, h1, h2, valid, k: int, m: int,
                         layout: str):
    idx = _bloom_idx(h1, h2, valid, k, m, layout)
    flat = idx.reshape(-1)
    vflat = jnp.broadcast_to(valid[:, None], idx.shape).reshape(-1)
    mine, safe = _local_mask(bits_local, flat, vflat)
    local = jnp.where(mine, bits_local[safe], 0).astype(jnp.int32)
    got = lax.psum(local, SHARD_AXIS).reshape(idx.shape)
    return jnp.all(got == 1, axis=-1) & valid


def _packed_hashes(packed, count, seed: int):
    valid = jnp.arange(packed.shape[0], dtype=jnp.int32) < count
    h1, h2 = murmur3_x64_128_u64(U64(packed[:, 1], packed[:, 0]), seed)
    return h1, h2, valid


def _bloom_map(body, mesh: Mesh, mutate: bool):
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(), P()),
        out_specs=(P(SHARD_AXIS), P()) if mutate else P(),
    )


@functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("k", "m", "seed", "mesh", "layout"))
def bloom_add_packed(bits, packed, count, k: int, m: int, seed: int,
                     mesh: Mesh, layout: str = "classic"):
    h1, h2, valid = _packed_hashes(packed, count, seed)
    body = functools.partial(_bloom_add_body, k=k, m=m, layout=layout)
    return _bloom_map(body, mesh, True)(bits, h1, h2, valid)


@functools.partial(
    jax.jit, static_argnames=("k", "m", "seed", "mesh", "layout"))
def bloom_contains_packed(bits, packed, count, k: int, m: int, seed: int,
                          mesh: Mesh, layout: str = "classic"):
    h1, h2, valid = _packed_hashes(packed, count, seed)
    body = functools.partial(_bloom_contains_body, k=k, m=m, layout=layout)
    return _bloom_map(body, mesh, False)(bits, h1, h2, valid)


@functools.partial(
    jax.jit, static_argnames=("k", "m", "seed", "mesh", "layout"))
def bloom_contains_count_packed(bits, packed, count, k: int, m: int,
                                seed: int, mesh: Mesh,
                                layout: str = "classic"):
    # graftlint: allow-int-reduce(summing a 0/1 mask over one batch; batches cap at MAX_BUCKET 2^21 << 2^31)
    return jnp.sum(bloom_contains_packed(
        bits, packed, count, k, m, seed, mesh, layout).astype(jnp.int32))


@functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("k", "m", "seed", "mesh", "layout"))
def bloom_add_bytes(bits, data, lengths, valid, k: int, m: int, seed: int,
                    mesh: Mesh, layout: str = "classic"):
    h1, h2 = murmur3_x64_128(data, lengths, seed)
    body = functools.partial(_bloom_add_body, k=k, m=m, layout=layout)
    return _bloom_map(body, mesh, True)(bits, h1, h2, valid)


@functools.partial(
    jax.jit, static_argnames=("k", "m", "seed", "mesh", "layout"))
def bloom_contains_bytes(bits, data, lengths, valid, k: int, m: int,
                         seed: int, mesh: Mesh, layout: str = "classic"):
    h1, h2 = murmur3_x64_128(data, lengths, seed)
    body = functools.partial(_bloom_contains_body, k=k, m=m, layout=layout)
    return _bloom_map(body, mesh, False)(bits, h1, h2, valid)


# -- lifecycle ---------------------------------------------------------------


def grow_bits(bits, new_nbits: int, mesh: Mesh) -> jax.Array:
    """Enlarge to physical_size(new_nbits), keeping bit positions — the
    SETBIT auto-grow analogue, resharded over the same mesh."""
    target = physical_size(new_nbits, mesh)
    n = bits.shape[0]
    if target <= n:
        return bits
    pad = jnp.zeros((target - n,), bits.dtype)
    return jax.device_put(
        jnp.concatenate([bits, pad]), bits_sharding(mesh))


def migrate_bits(bits, new_mesh: Mesh) -> jax.Array:
    """Re-shard onto a different mesh (topology change / device loss): one
    resharding device_put; XLA emits the all-to-all over ICI."""
    n = bits.shape[0]
    target = physical_size(n, new_mesh)
    if target != n:
        bits = jnp.concatenate(
            [bits, jnp.zeros((target - n,), bits.dtype)])
    return jax.device_put(bits, bits_sharding(new_mesh))
