"""Sharded HLL bank: [S, m] sketches row-sharded over a device mesh.

The multi-chip design (SURVEY.md §7 step 5 / BASELINE configs #4-5):

  * a bank of S sketches lives as one [S, m] int32 array with
    NamedSharding(P('shards', None)) — S/D rows per device, registers local,
    so every insert touches exactly one device's HBM;
  * inserts take a replicated key batch + per-key target row; inside
    shard_map each device masks the keys routed to its row range and
    scatter-maxes into its local rows — the analogue of cluster mode's
    "send each command to its slot's master" without any per-key host
    routing;
  * whole-bank PFMERGE = local row-max then `lax.pmax` over the shard axis —
    one ICI all-reduce replaces the reference's cross-slot PFMERGE fan-out
    (`RedissonHyperLogLog.countWith` + SlotCallback reduce).

Everything compiles to a single SPMD program per batch bucket; no
data-dependent shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from redisson_tpu.ops import hll
from redisson_tpu.ops.hashing import murmur3_x64_128_u64
from redisson_tpu.ops.u64 import U64
from redisson_tpu.parallel.mesh import SHARD_AXIS, bank_sharding


def make_bank(mesh: Mesh, num_sketches: int, m: int = hll.M) -> jax.Array:
    """Zero-initialized sharded [S, m] bank."""
    ndev = mesh.devices.size
    if num_sketches % ndev != 0:
        raise ValueError(f"num_sketches {num_sketches} not divisible by {ndev} devices")
    return jax.device_put(
        jnp.zeros((num_sketches, m), jnp.int32), bank_sharding(mesh)
    )


def _insert_local(bank_local, hi, lo, row, valid, seed: int,
                  pre_hashed: bool = False):
    """Per-device body: fold keys routed to this device's rows.

    bank_local: [S/D, m]; hi/lo/row/valid: full replicated batch.
    pre_hashed=True treats (hi, lo) as an already-computed murmur3 h1
    (byte keys hash host-side via the native batch murmur so local and pod
    modes agree bit-for-bit on identical inputs); False hashes the raw u64
    key on device (the int fast path).
    Returns (new_local, changed_local[S/D]) — a PER-ROW "any register
    raised" flag (concatenates to the global [S] vector across the shard
    axis), so a cross-sketch coalesced insert can give every target its own
    PFADD bool instead of one run-wide flag.
    """
    s_local, m = bank_local.shape
    dev = lax.axis_index(SHARD_AXIS)
    row_start = dev * s_local
    local_row = row - row_start
    mine = valid & (local_row >= 0) & (local_row < s_local)

    if pre_hashed:
        h1 = U64(hi, lo)
    else:
        h1, _ = murmur3_x64_128_u64(U64(hi, lo), seed)
    p = m.bit_length() - 1
    bucket, rank = hll.bucket_rank(h1, p)
    rank = jnp.where(mine, rank, 0)
    flat = bank_local.reshape(-1)
    safe_row = jnp.where(mine, local_row, 0)
    flat_idx = safe_row * m + bucket
    raised = (rank > flat[flat_idx]) & mine
    changed_local = jnp.zeros((s_local,), bool).at[safe_row].max(raised)
    return flat.at[flat_idx].max(rank).reshape(s_local, m), changed_local


@functools.partial(
    jax.jit, static_argnames=("mesh", "seed", "pre_hashed"), donate_argnums=(0,)
)
def bank_insert(bank, hi, lo, row, valid, mesh: Mesh, seed: int = 0,
                pre_hashed: bool = False):
    """Insert a replicated key batch into the sharded bank (one SPMD step).

    Returns (new_bank, changed_rows[S]) — per-row change flags vs
    pre-batch state (`changed_rows.any()` is the whole-batch bool).
    """
    fn = shard_map(
        functools.partial(_insert_local, seed=seed, pre_hashed=pre_hashed),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(), P(), P(), P()),
        out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS)),
    )
    return fn(bank, hi, lo, row, valid)


def _merge_local(bank_local):
    partial = jnp.max(bank_local, axis=0)  # [m] local row-max
    return lax.pmax(partial, SHARD_AXIS)[None, :]


@functools.partial(jax.jit, static_argnames=("mesh",))
def bank_merge_all(bank, mesh: Mesh):
    """PFMERGE across every sketch in the bank -> [m] merged registers.

    Local row-max on each device, then one pmax all-reduce over ICI.
    """
    fn = shard_map(
        _merge_local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None),),
        out_specs=P(SHARD_AXIS, None),
    )
    # Output is [D, m] (one identical merged row per device); take row 0.
    return fn(bank)[0]


@functools.partial(jax.jit, static_argnames=("mesh",))
def bank_count_all(bank, mesh: Mesh):
    """Union cardinality of the whole bank (merge + count, no mutation)."""
    return hll.count(bank_merge_all(bank, mesh))


@jax.jit
def bank_count_row(bank, row: jax.Array):
    """Cardinality of one sketch row (XLA inserts the cross-device gather)."""
    return hll.count(bank[row])


@functools.partial(jax.jit, static_argnames=("mesh",))
def bank_count_rows_merged(bank, rows, mesh: Mesh):
    """Union count over a static-shape row subset (padded with repeats)."""
    sub = bank[rows]  # [R, m] gather
    return hll.count(jnp.max(sub, axis=0))


@functools.partial(jax.jit, donate_argnums=(0,))
def bank_merge_rows(bank, rows, target):
    """PFMERGE `rows` (caller includes `target`) into row `target` over the
    sharded bank (XLA inserts the cross-device gather/update)."""
    merged = jnp.max(bank[rows], axis=0)
    return bank.at[target].set(merged)


@functools.partial(jax.jit, donate_argnums=(0,))
def bank_merge_count_rows(bank, rows, target):
    """Fused PFMERGE+PFCOUNT over the sharded bank: fold `rows` (includes
    `target`) into row `target` and estimate the union in one program —
    one dependent D2H sync on the blocking path (XLA inserts the
    cross-device gather/update for the row sharding)."""
    merged = jnp.max(bank[rows], axis=0)
    return bank.at[target].set(merged), hll.count(merged)


@functools.partial(jax.jit, donate_argnums=(0,))
def _absorb_host(bank, host_bank):
    return jnp.maximum(bank, host_bank.astype(jnp.int32))


def bank_absorb_host(bank, host_u8, mesh: Mesh) -> jax.Array:
    """Max-merge a host-folded [S, m] uint8 bank mirror into the sharded
    device bank — the absorb half of the streaming host-ingest path
    (native.hll_fold_u64_rows folds the key stream on host; one bank
    upload per absorb interval replaces 8 B/key of link traffic)."""
    return _absorb_host(bank, jax.device_put(host_u8, bank_sharding(mesh)))


def zero_row(bank, row: int) -> jax.Array:
    """Reset one sketch row (pod-mode DEL of an HLL)."""
    return bank.at[row].set(0)


def grow_bank(bank, new_capacity: int, mesh: Mesh) -> jax.Array:
    """Enlarge [S, m] -> [S', m] keeping row indices and shard layout —
    elastic capacity (the slot-add analogue). Row data round-trips through
    the sharding machinery, not the host."""
    s, m = bank.shape
    if new_capacity < s:
        raise ValueError(f"cannot shrink {s} -> {new_capacity}")
    if new_capacity == s:
        return bank
    pad = jnp.zeros((new_capacity - s, m), bank.dtype)
    return jax.device_put(
        jnp.concatenate([bank, pad], axis=0), bank_sharding(mesh))


def migrate_bank(bank, new_mesh: Mesh) -> jax.Array:
    """Re-shard the bank onto a different mesh (topology change): the
    reference's live slot migration becomes one resharding device_put
    (XLA emits the all-to-all over ICI)."""
    if bank.shape[0] % new_mesh.devices.size:
        raise ValueError(
            f"bank rows {bank.shape[0]} not divisible by "
            f"{new_mesh.devices.size} devices")
    return jax.device_put(bank, bank_sharding(new_mesh))


def full_step(bank, hi, lo, row, valid, mesh: Mesh, seed: int = 0):
    """One complete 'training step': sharded insert + global merge-count.

    This is the flagship multi-chip program: scatter to shards over their
    local HBM, then an ICI pmax all-reduce and estimator — the
    dryrun_multichip entry exercises exactly this.
    """
    bank, _ = bank_insert(bank, hi, lo, row, valid, mesh, seed)
    est = bank_count_all(bank, mesh)
    return bank, est
