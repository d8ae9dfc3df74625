"""Jitted device entry points for the sketch engine.

Each function is the fused "one device call" a microbatch compiles to:
hash -> bucket/rank -> scatter, or hash -> k-indexes -> scatter/gather, with
a validity mask so the L2 executor can pad batches to static bucket sizes
without recompiles (pad-to-bucket, SURVEY.md §7 "dispatch amortization").

Masking rules (all no-ops on padded lanes):
  * HLL insert: padded rank forced to 0; registers hold >= 0 so max(., 0)
    never changes state.
  * Bit set: padded index forced to 0 with set-value semantics of max(., 0).
  * Gathers (contains/getbit): padded lanes read index 0; results sliced off
    host-side.

State arguments are donated so XLA reuses the HBM buffer — the register
array never round-trips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from redisson_tpu.ops import bitset, bloom, hashing, hll
from redisson_tpu.ops import pallas_kernels as pk
from redisson_tpu.ops.u64 import U64

# Batch-size buckets: powers of two between MIN_BUCKET and MAX_BUCKET keys.
MIN_BUCKET = 1 << 10
MAX_BUCKET = 1 << 21


def bucket_size(n: int) -> int:
    b = MIN_BUCKET
    while b < n and b < MAX_BUCKET:
        b <<= 1
    return b


def chunk_spans(n: int, chunk: int = None):
    """[0, n) split into device-call-sized spans (a single op may exceed the
    coalescing cap; the backend loops the kernel over these)."""
    if chunk is None:
        chunk = MAX_BUCKET  # read at call time (tests shrink it)
    return [(s, min(s + chunk, n)) for s in range(0, max(n, 1), chunk)] if n else []


def pad_bytes(data, lengths):
    """Pad [N, W] byte batch + lengths to (bucket, W) with a valid mask."""
    import numpy as np

    n = data.shape[0]
    b = bucket_size(n)
    if n == b:
        return data, lengths, np.ones((b,), bool)
    pdata = np.zeros((b, data.shape[1]), np.uint8)
    pdata[:n] = data
    plengths = np.zeros((b,), np.int32)
    plengths[:n] = lengths
    valid = np.zeros((b,), bool)
    valid[:n] = True
    return pdata, plengths, valid


def pad_rows(arr):
    """Pad [n, ...] rows to the bucket size along axis 0. No valid mask is
    built — count-masking kernels (hll_add_packed) mask on device."""
    import numpy as np

    n = arr.shape[0]
    b = bucket_size(n)
    if n == b:
        return arr, n
    out = np.zeros((b,) + arr.shape[1:], arr.dtype)
    out[:n] = arr
    return out, n


def pad_ints(arr, fill=0):
    import numpy as np

    n = arr.shape[0]
    b = bucket_size(n)
    if n == b:
        return arr, np.ones((b,), bool)
    out = np.full((b,) + arr.shape[1:], fill, arr.dtype)
    out[:n] = arr
    valid = np.zeros((b,), bool)
    valid[:n] = True
    return out, valid


# ---------------------------------------------------------------------------
# HLL
# ---------------------------------------------------------------------------


def _hll_h1_u64(x, seed: int, family: str):
    """The HLL hash for 8-byte LE keys by family: 'm3' = murmur3 x64 128
    low half (the framework's native family); 'redis' = MurmurHash64A
    (0xadc83b19) — exactly what a real server's PFADD computes
    (hyperloglog.c hllPatLen), so registers stay server-mergeable."""
    if family == "redis":
        return hashing.murmur2_64a_u64(x)
    h1, _ = hashing.murmur3_x64_128_u64(x, seed)
    return h1


def _hll_h1_bytes(data, lengths, seed: int, family: str):
    if family == "redis":
        return hashing.murmur2_64a(data, lengths)
    h1, _ = hashing.murmur3_x64_128(data, lengths, seed)
    return h1


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("impl", "seed", "family"))
def hll_add_bytes(regs, data, lengths, valid, impl: str = "scatter",
                  seed: int = 0, family: str = "m3"):
    """PFADD of a padded byte-key batch. Returns (new_regs, changed)."""
    h1 = _hll_h1_bytes(data, lengths, seed, family)
    return _hll_add(regs, h1, valid, impl)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("impl", "seed", "family"))
def hll_add_u64(regs, hi, lo, valid, impl: str = "scatter", seed: int = 0,
                family: str = "m3"):
    """PFADD of a padded uint64-key batch (8-byte LE fast path)."""
    h1 = _hll_h1_u64(U64(hi, lo), seed, family)
    return _hll_add(regs, h1, valid, impl)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("impl", "seed", "family"))
def hll_add_packed(regs, packed, count, impl: str = "scatter", seed: int = 0,
                   family: str = "m3"):
    """PFADD of a uint64-key batch shipped as its raw little-endian uint32
    view `[n, 2]` ([:, 0]=lo, [:, 1]=hi) — the zero-copy ingest path: the
    client transfers the key buffer as-is and the lane split + validity mask
    (`iota < count`, a traced scalar so ragged tails don't recompile) happen
    on device. This is what makes the 100M/s host path feasible: per batch
    the host touches only the 8 B/key payload once, for the DMA."""
    valid = jnp.arange(packed.shape[0], dtype=jnp.int32) < count
    h1 = _hll_h1_u64(U64(packed[:, 1], packed[:, 0]), seed, family)
    return _hll_add(regs, h1, valid, impl)


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_absorb(regs, folded_u8):
    """Merge a host-folded uint8 sketch into the device registers.

    The device half of the transfer-adaptive ingest path: when the
    host->device link is slow, the backend folds the key batch into 16 KB
    of registers natively (native.hll_fold_u64) and ships only the sketch —
    the same move-the-reduction-across-the-slow-link design as cross-shard
    PFMERGE over ICI. Returns (new_regs, changed)."""
    f = folded_u8.astype(jnp.int32)
    return jnp.maximum(regs, f), jnp.any(f > regs)


def _hll_add(regs, h1, valid, impl):
    p = regs.shape[0].bit_length() - 1
    bucket, rank = hll.bucket_rank(h1, p)
    rank = jnp.where(valid, rank, 0)
    new = _insert_impl(regs, bucket, rank, valid, impl)
    # changed: vs pre-batch state; regs is donated so compute before return.
    changed = jnp.any(new != regs)
    return new, changed


def _insert_impl(regs, bucket, rank, valid, impl):
    """One register-array insert, by strategy: 'scatter' (XLA combining
    scatter), 'sort' (sort-compress + small scatter), 'segment' (the
    ingest subsystem's Pallas segmented-scatter on TPU, its XLA
    sort-compress fallback elsewhere). Padded lanes carry rank 0."""
    if impl == "scatter":
        return hll.insert_scatter(regs, bucket, rank)
    bucket = jnp.where(valid, bucket, 0)
    if impl == "segment":
        from redisson_tpu.ingest import kernels as ingest_kernels

        return ingest_kernels.segmented_hll_add(regs, bucket, rank)
    return hll.insert_sorted(regs, bucket, rank)


@jax.jit
def hll_count(regs):
    return hll.count(regs)


@jax.jit
def hll_merge(dst, src):
    return jnp.maximum(dst, src)


def hll_merge_all(arrays):
    """Merge a python list of register arrays (one stacked bank reduce)."""
    if len(arrays) == 1:
        return arrays[0]
    if len(arrays) == 2:
        return hll_merge(arrays[0], arrays[1])
    return hll_merge_stack(jnp.stack(arrays))


@jax.jit
def hll_merge_stack(stack):
    """PFMERGE over an [S, m] bank (pallas streaming kernel on TPU)."""
    if pk.use_pallas():
        return pk.merge_stack(stack)
    return jnp.max(stack, axis=0)


@jax.jit
def hll_count_merged(stack):
    """Count over [S, m] pre-stacked sketches without mutating them."""
    return hll.count(hll_merge_stack(stack))


# ---------------------------------------------------------------------------
# HLL bank — named sketches as rows of ONE [S, m] device array.
#
# Single-chip analogue of parallel/sharded.py's mesh bank: every named HLL is
# a row, so multi-sketch PFMERGE/PFCOUNT (the reference's first-class
# mergeWith/countWith API, RedissonHyperLogLog.java:40-97) compiles to one
# gather + row-max kernel over an index vector instead of a python-side
# jnp.stack of S separate handles (r3: 183 ms for 256 sketches — almost all
# jit argument-flattening overhead, not compute). Inserts scatter-max at flat
# index row*m + bucket; the `rows` variants carry a per-key target row so a
# single device call can serve keys for many different sketches (the
# pipelined-PFADD-across-256-sketches shape).
# ---------------------------------------------------------------------------


def hll_bank_make(capacity: int, m: int = None) -> jnp.ndarray:
    if m is None:
        m = hll.M
    return jnp.zeros((capacity, m), jnp.int32)


def _bank_add(bank, h1, rows, valid):
    """Multi-target insert. Returns (new_bank, changed_rows[S]) — changed
    is PER ROW, so a cross-sketch coalesced run can give every op its own
    PFADD bool (Redis semantics: did THIS key's sketch change) instead of
    leaking one run-wide flag across targets.

    changed comes from a whole-bank row compare, NOT a per-key gather of
    the old registers: XLA lowers random 1-D gathers on TPU near-serially
    (the gather formulation measured 2.6x slower end to end)."""
    s, m = bank.shape
    p = m.bit_length() - 1
    bucket, rank = hll.bucket_rank(h1, p)
    rank = jnp.where(valid, rank, 0)  # padded lanes: rank 0 never raises
    idx = jnp.where(valid, rows, 0) * m + bucket
    new = bank.reshape(-1).at[idx].max(rank).reshape(s, m)
    changed_rows = jnp.any(new != bank, axis=1)
    return new, changed_rows


def _bank_add_row(bank, h1, row, valid, impl: str = "scatter"):
    """Single-target insert (scalar `row`): slice the row out, insert into
    the 16K row (the flat single-sketch kernel's cost profile), write
    it back with a dynamic update — ~2.7x the throughput of routing a
    scalar row through the multi-target path (91M vs 34M inserts/s/chip
    measured at 1M-key batches, S=256). `impl` picks the row insert
    (see _insert_impl); the multi-target _bank_add stays on the flat
    scatter — its row*m+bucket codes would overflow the segmented
    kernel's int32 code space for large banks."""
    s, m = bank.shape
    p = m.bit_length() - 1
    bucket, rank = hll.bucket_rank(h1, p)
    rank = jnp.where(valid, rank, 0)
    old_row = jax.lax.dynamic_index_in_dim(bank, row, keepdims=False)
    new_row = _insert_impl(old_row, bucket, rank, valid, impl)
    new = jax.lax.dynamic_update_index_in_dim(bank, new_row, row, axis=0)
    changed_rows = jnp.zeros((s,), bool).at[row].set(
        jnp.any(new_row != old_row))
    return new, changed_rows


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("seed", "family", "impl"))
def hll_bank_add_packed(bank, packed, count, row, seed: int = 0,
                        family: str = "m3", impl: str = "scatter"):
    """Single-target PFADD into bank row `row` (a traced scalar — no per-key
    row vector ships over the link, preserving the 8 B/key transfer profile
    of the flat hll_add_packed path)."""
    valid = jnp.arange(packed.shape[0], dtype=jnp.int32) < count
    h1 = _hll_h1_u64(U64(packed[:, 1], packed[:, 0]), seed, family)
    return _bank_add_row(bank, h1, row, valid, impl)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("seed", "family"))
def hll_bank_add_packed_rows(bank, packed, rows, count, seed: int = 0,
                             family: str = "m3"):
    """Multi-target PFADD: per-key target row (cross-sketch coalesced run)."""
    valid = jnp.arange(packed.shape[0], dtype=jnp.int32) < count
    h1 = _hll_h1_u64(U64(packed[:, 1], packed[:, 0]), seed, family)
    return _bank_add(bank, h1, rows, valid)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("seed", "family"))
def hll_bank_add_u64_rows(bank, hi, lo, rows, valid, seed: int = 0,
                          family: str = "m3"):
    h1 = _hll_h1_u64(U64(hi, lo), seed, family)
    return _bank_add(bank, h1, rows, valid)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("seed", "family", "impl"))
def hll_bank_add_u64(bank, hi, lo, valid, row, seed: int = 0,
                     family: str = "m3", impl: str = "scatter"):
    """Single-target u64 PFADD (scalar row broadcast on device — no
    4 B/key row vector crosses the link)."""
    h1 = _hll_h1_u64(U64(hi, lo), seed, family)
    return _bank_add_row(bank, h1, row, valid, impl)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("seed", "family"))
def hll_bank_add_bytes_rows(bank, data, lengths, rows, valid, seed: int = 0,
                            family: str = "m3"):
    h1 = _hll_h1_bytes(data, lengths, seed, family)
    return _bank_add(bank, h1, rows, valid)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("seed", "family", "impl"))
def hll_bank_add_bytes(bank, data, lengths, valid, row, seed: int = 0,
                       family: str = "m3", impl: str = "scatter"):
    """Single-target byte-key PFADD (scalar row, see hll_bank_add_u64)."""
    h1 = _hll_h1_bytes(data, lengths, seed, family)
    return _bank_add_row(bank, h1, row, valid, impl)


@jax.jit
def hll_bank_row(bank, row):
    """One row's registers as a fresh array (export/snapshot: safe against a
    later donating insert invalidating the bank buffer)."""
    return bank[row]


@jax.jit
def hll_bank_count(bank, row):
    return hll.count(bank[row])


@jax.jit
def hll_bank_count_rows(bank, rows):
    """Union count over a row subset — THE countWith kernel. `rows` may be
    padded with repeats (max is idempotent) to stay shape-static."""
    return hll.count(jnp.max(bank[rows], axis=0))


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_merge_rows(bank, rows, target):
    """PFMERGE rows (caller includes `target` in `rows`) into row `target`."""
    merged = jnp.max(bank[rows], axis=0)
    return bank.at[target].set(merged)


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_merge_count_rows(bank, rows, target):
    """Fused PFMERGE + PFCOUNT: fold `rows` (caller includes `target`) into
    row `target` AND estimate the merged cardinality in ONE device program,
    so a blocking merge_with+count pays ONE dependent D2H sync instead of
    two (VERDICT r4 next #3: config 3's blocking shot was ~3 link RTTs; the
    reference does it in one round trip by pipelining PFMERGE+PFCOUNT in a
    batch, RedissonHyperLogLog.java:78-97)."""
    merged = jnp.max(bank[rows], axis=0)
    return bank.at[target].set(merged), hll.count(merged)


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_absorb_rows(bank, regs_u8, rows):
    """Max-merge host-folded sketches [R, m] into bank rows [R] — the bank
    half of the transfer-adaptive ingest (one kernel absorbs a whole
    cross-sketch hostfold run). Returns (new_bank, changed[R]) with a
    per-source changed flag (the PFADD bool for that source's target)."""
    s, m = bank.shape
    f = regs_u8.astype(jnp.int32)
    flat = bank.reshape(-1)
    idx = (rows[:, None] * m + jnp.arange(m, dtype=rows.dtype)[None, :])
    changed = jnp.any(f > flat[idx.reshape(-1)].reshape(f.shape), axis=1)
    return flat.at[idx.reshape(-1)].max(f.reshape(-1)).reshape(s, m), changed


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_set_row(bank, regs, row):
    """Overwrite one row (hll_import / checkpoint restore)."""
    return bank.at[row].set(regs.astype(jnp.int32))


@jax.jit
def hll_bank_rows_u8(bank, rows):
    """Gather bank rows as uint8 register images (registers are 0..64, so
    the narrowing is lossless) — the old-state side of a delta-merge
    stack row."""
    return bank[rows].astype(jnp.uint8)


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_set_rows(bank, regs_u8, rows):
    """Overwrite bank rows [R] with merged [R, m] uint8 register images —
    the writeback half of the fused delta merge (rows are unique within a
    run, so a flat set scatter is race-free)."""
    s, m = bank.shape
    flat = bank.reshape(-1)
    idx = rows[:, None] * m + jnp.arange(m, dtype=rows.dtype)[None, :]
    return flat.at[idx.reshape(-1)].set(
        regs_u8.astype(jnp.int32).reshape(-1)).reshape(s, m)


@functools.partial(jax.jit, donate_argnums=(0,))
def hll_bank_zero_row(bank, row):
    return bank.at[row].set(0)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("new_cap",))
def hll_bank_grow(bank, new_cap: int):
    """Elastic capacity: [S, m] -> [S', m], row indices stable."""
    s, m = bank.shape
    return jnp.zeros((new_cap, m), bank.dtype).at[:s].set(bank)


def pad_rows_repeat(rows):
    """Pad a row-index vector to the next power of two by repeating the
    first element (gather+max targets: repeats are idempotent, shapes stay
    static per size class — no MIN_BUCKET floor; a 2-name countWith must
    not gather 1024 rows)."""
    import numpy as np

    n = rows.shape[0]
    b = 1 << max(0, int(n - 1).bit_length())
    if n == b:
        return rows
    out = np.full((b,), rows[0], rows.dtype)
    out[:n] = rows
    return out


# ---------------------------------------------------------------------------
# Mesh collectives (cluster data_plane="mesh")
#
# The bank is row-sharded across a 1-D device mesh (parallel/mesh.py
# SLOT_AXIS). Cross-shard PFMERGE / PFCOUNT / DBSIZE then run as shard_map
# collectives: each device max-folds the requested rows IT owns, one pmax
# hop combines the partials across the mesh, and the target row's owner
# scatters the merged registers back into its local block. No register
# image ever crosses the host link (the stacks plane's export ->
# np.maximum.reduce -> import round-trip).
# ---------------------------------------------------------------------------

from jax import shard_map as _shard_map

from jax.sharding import PartitionSpec as _P


@functools.lru_cache(maxsize=None)
def _mesh_collectives(mesh):
    """Per-mesh compiled collective entry points.

    Cached on the Mesh object (hashable; parallel/mesh.get_mesh returns a
    stable instance per device set) so repeated calls reuse the jit cache
    instead of re-wrapping shard_map every dispatch."""
    axis = mesh.axis_names[0]

    def _local_fold(bank_local, rows):
        """Max-fold the globally-indexed `rows` this device owns; returns
        the pmax-combined merged registers (replicated) plus this device's
        row base/extent for the writeback scatter."""
        s_local = bank_local.shape[0]
        base = jax.lax.axis_index(axis) * s_local
        lrows = rows - base
        own = (lrows >= 0) & (lrows < s_local)
        gathered = bank_local[jnp.clip(lrows, 0, s_local - 1)]
        partial = jnp.max(jnp.where(own[:, None], gathered, 0), axis=0)
        return jax.lax.pmax(partial, axis), base, s_local

    def _merge_body(bank_local, rows, target):
        merged, base, s_local = _local_fold(bank_local, rows)
        tl = target - base
        ti = jnp.clip(tl, 0, s_local - 1)
        t_own = (tl >= 0) & (tl < s_local)
        upd = jnp.where(t_own, merged, bank_local[ti])
        return bank_local.at[ti].set(upd)

    def _merge_count_body(bank_local, rows, target):
        merged, base, s_local = _local_fold(bank_local, rows)
        tl = target - base
        ti = jnp.clip(tl, 0, s_local - 1)
        t_own = (tl >= 0) & (tl < s_local)
        upd = jnp.where(t_own, merged, bank_local[ti])
        return bank_local.at[ti].set(upd), hll.count(merged)

    def _count_body(bank_local, rows):
        merged, _, _ = _local_fold(bank_local, rows)
        return hll.count(merged)

    def _occupancy_body(bank_local):
        # DBSIZE-style row occupancy: per-device count of non-empty rows,
        # one psum hop for the mesh-wide total.
        # graftlint: allow-int-reduce(0/1 row mask; bounded by bank capacity << 2^31)
        local = jnp.sum(jnp.any(bank_local != 0, axis=1).astype(jnp.int32))
        return jax.lax.psum(local, axis)

    bank_spec = _P(axis, None)
    rep = _P()
    # The jits below close over the mesh, so they cannot live at module
    # level; `_mesh_collectives` is lru_cached per mesh, so each compiles
    # exactly once per device topology.
    # graftlint: allow-recompile(constructed once per mesh via lru_cache)
    merge = jax.jit(_shard_map(
        _merge_body, mesh=mesh, in_specs=(bank_spec, rep, rep),
        out_specs=bank_spec), donate_argnums=(0,))
    # graftlint: allow-recompile(constructed once per mesh via lru_cache)
    merge_count = jax.jit(_shard_map(
        _merge_count_body, mesh=mesh, in_specs=(bank_spec, rep, rep),
        out_specs=(bank_spec, rep)), donate_argnums=(0,))
    # graftlint: allow-recompile(constructed once per mesh via lru_cache)
    count = jax.jit(_shard_map(
        _count_body, mesh=mesh, in_specs=(bank_spec, rep),
        out_specs=rep))
    # graftlint: allow-recompile(constructed once per mesh via lru_cache)
    occupancy = jax.jit(_shard_map(
        _occupancy_body, mesh=mesh, in_specs=(bank_spec,),
        out_specs=rep))
    return {"merge": merge, "merge_count": merge_count, "count": count,
            "occupancy": occupancy}


def hll_bank_merge_rows_collective(bank, rows, target, *, mesh):
    """PFMERGE `rows` into row `target` on a mesh-sharded bank — the
    device-side fold stays on the shard axis; the only cross-device
    traffic is one pmax of the m merged registers."""
    return _mesh_collectives(mesh)["merge"](
        bank, jnp.asarray(rows, jnp.int32), jnp.int32(target))


def hll_bank_merge_count_rows_collective(bank, rows, target, *, mesh):
    """Fused collective PFMERGE + PFCOUNT (one launch, one pmax hop)."""
    return _mesh_collectives(mesh)["merge_count"](
        bank, jnp.asarray(rows, jnp.int32), jnp.int32(target))


def hll_bank_count_rows_collective(bank, rows, *, mesh):
    """Union cardinality over rows of a mesh-sharded bank (countWith)."""
    return _mesh_collectives(mesh)["count"](
        bank, jnp.asarray(rows, jnp.int32))


def hll_bank_occupancy_collective(bank, *, mesh):
    """Mesh-wide non-empty row count (DBSIZE analogue) via one psum."""
    return _mesh_collectives(mesh)["occupancy"](bank)


@jax.jit
def bitset_pack(bits):
    """[m] uint8 cells -> packed bytes (numpy packbits big-endian order:
    absolute bit i -> byte i>>3, bit 7-(i&7)). The device half of pulling a
    bloom/bitset to the host mirror: 1 bit/bit over the link, not 1 byte."""
    return bitset.pack(bits)


@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_absorb_packed(bits, packed):
    """OR a packed (big-endian) bitmap into [m] uint8 cells — the device
    half of the bloom hostfold absorb."""
    unpacked = bitset.unpack(packed, bits.shape[0])
    return jnp.maximum(bits, unpacked.astype(bits.dtype))


# ---------------------------------------------------------------------------
# Delta ingest — device half (ingest/delta.py is the host half).
#
# Every host-folded plane staged in one pipeline window becomes a row of a
# [T, L] uint8 cell stack (L = max cell count, zero-padded; zeros are an
# identity under max), merged against the matching old-state rows in ONE
# fused elementwise-max launch. OR == max on 0/1 bit cells and HLL
# registers are 0..64, so one kernel covers all three delta kinds.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cells",))
def delta_unpack(packed, cells: int):
    """Packed big-endian byte plane -> [cells] uint8 cells (bit i lives at
    byte i>>3, bit 7-(i&7) — numpy packbits order, see bitset_pack)."""
    return bitset.unpack(packed, cells)


@functools.partial(jax.jit, static_argnames=("nbytes",))
def delta_scatter_bytes(idx, val, nbytes: int):
    """Expand a sparse (idx, val) byte-plane encoding to its dense form.
    Padded entries carry (0, 0): .at[0].max(0) is a no-op."""
    return jnp.zeros((nbytes,), jnp.uint8).at[idx].max(val)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def delta_merge_stack(old, delta):
    """ONE fused multi-target delta merge: elementwise max of the [T, L]
    uint8 old-state stack against the delta stack -> (merged [T, L],
    changed [T] bool). Pallas streaming kernel on TPU, XLA elsewhere.
    Both stacks are per-window temporaries, so both donate."""
    if pk.use_pallas():
        return pk.delta_merge(old, delta)
    merged = jnp.maximum(old, delta)
    return merged, jnp.any(merged != old, axis=1)


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("n_hll", "lanes", "want_old", "mesh"))
def tape_apply(bank, wire, table, hll_rows, store_old, *,
               n_hll: int, lanes: int, want_old: bool = False, mesh=None):
    """Consume one encoded window tape in a SINGLE device call.

    The whole window retire — bank-row gather, old-state assembly,
    per-entry op_code decode + merge (ops/window_kernel), pre-merge bit
    pack for SETBIT results, and the bank writeback scatter — compiles
    into one executable, so a mixed hll/bloom/bitset window costs one
    dispatch instead of the delta path's gather + per-plane decode +
    merge + writeback launch train.

    Args: ``bank`` [S, m] int32 (dummy when ``n_hll`` is 0), ``wire``
    uint8 [T2, W] and ``table`` int32 [T2, 4] from the tape encode,
    ``hll_rows`` int32 [h2] bank rows repeat-padded with row 0 (pad
    writes are idempotent — they rewrite row 0 with its own merged
    registers), ``store_old`` a tuple of the store-backed entries' cell
    arrays in arena order (NOT donated — they are live store state until
    the host swaps in the merged rows). Returns ``(bank, merged [T2, L],
    changed [T2] bool, old_packed [T2, L//8] | None)`` where
    ``old_packed`` holds the PRE-merge bits of every row (big-endian
    packbits order) for bitset old-bit reads. With ``mesh`` (the mesh
    data plane's sharded bank) every device merges the replicated window:
    a Mosaic kernel cannot be partitioned automatically."""
    from redisson_tpu.ops import window_kernel as wk

    t2 = table.shape[0]
    m = bank.shape[1]
    rows = []
    if n_hll:
        g = bank[hll_rows].astype(jnp.uint8)
        if m < lanes:
            g = jnp.pad(g, ((0, 0), (0, lanes - m)))
        rows.extend(g[i] for i in range(n_hll))
    for s in store_old:
        c = s.shape[0]
        s = s.astype(jnp.uint8)
        if c < lanes:
            s = jnp.pad(s, (0, lanes - c))
        rows.append(s)
    zero = jnp.zeros((lanes,), jnp.uint8)
    rows.extend([zero] * (t2 - len(rows)))
    old = jnp.stack(rows)
    old_packed = None
    if want_old:
        old_packed = bitset.pack(old.reshape(-1)).reshape(t2, lanes // 8)
    merge = wk.window_merge
    if mesh is not None:
        merge = _shard_map(merge, mesh=mesh, in_specs=(_P(),) * 3,
                           out_specs=(_P(), _P()), check_vma=False)
    merged, changed = merge(old, wire, table)
    if n_hll:
        h2 = hll_rows.shape[0]
        sel = jnp.where(jnp.arange(h2) < n_hll, jnp.arange(h2), 0)
        regs = merged[sel][:, :m]
        s_cap = bank.shape[0]
        flat = bank.reshape(-1)
        idx = (hll_rows[:, None] * m
               + jnp.arange(m, dtype=hll_rows.dtype)[None, :])
        bank = flat.at[idx.reshape(-1)].set(
            regs.astype(jnp.int32).reshape(-1)).reshape(s_cap, m)
    return bank, merged, changed, old_packed


# ---------------------------------------------------------------------------
# BitSet
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_set(bits, idx, valid):
    """SETBIT batch -> (new_bits, old_values). Padded lanes read idx 0."""
    idx = jnp.where(valid, idx, 0)
    old = bits[idx]
    new = bits.at[idx].max(jnp.where(valid, jnp.uint8(1), jnp.uint8(0)))
    return new, old


@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_clear(bits, idx, valid):
    idx = jnp.where(valid, idx, 0)
    old = bits[idx]
    new = bits.at[idx].min(jnp.where(valid, jnp.uint8(0), jnp.uint8(1)))
    return new, old


@jax.jit
def bitset_get(bits, idx, valid):
    return bits[jnp.where(valid, idx, 0)]


@jax.jit
def bitset_cardinality_partials(bits):
    """Device half of BITCOUNT: overflow-proof int32 partials (pallas
    per-block partials on TPU, chunked XLA sums elsewhere)."""
    if pk.use_pallas():
        return pk.popcount_partials(bits)
    return bitset.cardinality_partials(bits)


def bitset_cardinality(bits) -> int:
    """BITCOUNT, exact past 2^31 set bits: partials combine host-side
    in python ints (int32 totals wrap negative there)."""
    return bitset.combine_partials(bitset_cardinality_partials(bits))


@functools.partial(jax.jit, static_argnames=("op",))
def bitset_bitop(stack, op: str):
    """BITOP AND|OR|XOR over [K, n] stacked operands -> [n]."""
    if pk.use_pallas():
        return pk.bitop_cells(stack, op)
    fn = {"and": jnp.bitwise_and, "or": jnp.bitwise_or, "xor": jnp.bitwise_xor}[op]
    acc = stack[0]
    for k in range(1, stack.shape[0]):
        acc = fn(acc, stack[k])
    return acc


@jax.jit
def bitset_length_partials(bits):
    """Device half of lengthAsync: per-chunk int32 'highest set bit + 1'
    local offsets — absolute positions (which wrap int32 past 2^31 bits)
    are only formed host-side by `bitset.combine_length`."""
    return bitset.length_partials(bits)


def bitset_length(bits) -> int:
    """Index of highest set bit + 1, exact past 2^31 bits. Blocks; the
    backend dispatch path stages `bitset_length_partials` asynchronously
    instead and combines in the completer."""
    return bitset.combine_length(bitset_length_partials(bits))


@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_not_masked(bits, n):
    """BITOP NOT over cells [0, n) only — redis NOT operates on the string's
    written bytes (STRLEN), not the backing allocation; cells past the
    written extent stay 0 (conformance vs RedissonBitSetTest.java:57-64)."""
    pos = jnp.arange(bits.shape[0], dtype=jnp.uint32)
    return jnp.where(pos < n.astype(jnp.uint32), jnp.uint8(1) - bits, bits)


# ---------------------------------------------------------------------------
# Bloom
# ---------------------------------------------------------------------------


def _bloom_add(bits, h1, h2, valid, k: int, m: int, impl: str = "scatter"):
    """Shared add core: k-index double hashing -> masked set ->
    (new_bits, added_mask). Padded lanes write index 0 with value 0.
    `impl='segment'` routes the set through the ingest subsystem's
    segment-or (invalid lanes map to the one-past-end cell, which both
    the kernel and its lax fallback drop)."""
    idx = bloom.indexes(h1, h2, k, m)
    idx = jnp.where(valid[:, None], idx, 0)
    old = bits[idx.reshape(-1)].reshape(idx.shape)
    vals = jnp.broadcast_to(valid[:, None], idx.shape)
    if impl == "segment":
        from redisson_tpu.ingest import kernels as ingest_kernels

        flat = jnp.where(vals, idx, bits.shape[0]).reshape(-1)
        new = ingest_kernels.segmented_bits_set(bits, flat)
    else:
        new = bits.at[idx.reshape(-1)].max(
            vals.astype(jnp.uint8).reshape(-1))
    added = jnp.any(old == 0, axis=-1) & valid
    return new, added


def _bloom_contains(bits, h1, h2, valid, k: int, m: int):
    idx = bloom.indexes(h1, h2, k, m)
    idx = jnp.where(valid[:, None], idx, 0)
    return bloom.contains(bits, idx) & valid


def _packed_hashes(packed, count, seed):
    """(h1, h2, valid) for the raw-LE-uint32-view key layout ([:,0]=lo,
    [:,1]=hi) — identical hashing to the byte path on 8-byte LE keys."""
    valid = jnp.arange(packed.shape[0], dtype=jnp.int32) < count
    h1, h2 = hashing.murmur3_x64_128_u64(U64(packed[:, 1], packed[:, 0]), seed)
    return h1, h2, valid


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("k", "m", "seed", "impl")
)
def bloom_add_bytes(bits, data, lengths, valid, k: int, m: int, seed: int = 0,
                    impl: str = "scatter"):
    """Bloom add of a padded byte-key batch -> (new_bits, added_mask)."""
    h1, h2 = hashing.murmur3_x64_128(data, lengths, seed)
    return _bloom_add(bits, h1, h2, valid, k, m, impl)


@functools.partial(jax.jit, static_argnames=("k", "m", "seed"))
def bloom_contains_bytes(bits, data, lengths, valid, k: int, m: int, seed: int = 0):
    h1, h2 = hashing.murmur3_x64_128(data, lengths, seed)
    return _bloom_contains(bits, h1, h2, valid, k, m)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("k", "m", "seed", "impl")
)
def bloom_add_packed(bits, packed, count, k: int, m: int, seed: int = 0,
                     impl: str = "scatter"):
    """Bloom add of uint64 keys in the zero-copy packed layout."""
    h1, h2, valid = _packed_hashes(packed, count, seed)
    return _bloom_add(bits, h1, h2, valid, k, m, impl)


@functools.partial(jax.jit, static_argnames=("k", "m", "seed"))
def bloom_contains_packed(bits, packed, count, k: int, m: int, seed: int = 0):
    h1, h2, valid = _packed_hashes(packed, count, seed)
    return _bloom_contains(bits, h1, h2, valid, k, m)


# -- blocked bloom (ops/bloom.py BLOCK_BITS docstring) ----------------------


def _blocked_add(bits, h1, h2, valid, k: int, m: int):
    block, pos = bloom.blocked_indexes(h1, h2, k, m)
    idx = bloom.blocked_absolute(block, pos)
    idx = jnp.where(valid[:, None], idx, 0)
    # Same masking as classic _bloom_add: padded lanes write index 0 with
    # VALUE 0 (an unmasked max(1) would spuriously set absolute bit 0).
    old = bits[idx.reshape(-1)].reshape(idx.shape)
    vals = jnp.broadcast_to(valid[:, None], idx.shape)
    new_bits = bits.at[idx.reshape(-1)].max(vals.astype(jnp.uint8).reshape(-1))
    added = jnp.any(old == 0, axis=-1) & valid
    return new_bits, added


def _blocked_contains(bits, h1, h2, valid, k: int, m: int):
    block, pos = bloom.blocked_indexes(h1, h2, k, m)
    block = jnp.where(valid, block, 0)
    return bloom.blocked_contains(bits, block, pos) & valid


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("k", "m", "seed")
)
def blocked_bloom_add_packed(bits, packed, count, k: int, m: int, seed: int = 0):
    h1, h2, valid = _packed_hashes(packed, count, seed)
    return _blocked_add(bits, h1, h2, valid, k, m)


@functools.partial(jax.jit, static_argnames=("k", "m", "seed"))
def blocked_bloom_contains_packed(bits, packed, count, k: int, m: int, seed: int = 0):
    h1, h2, valid = _packed_hashes(packed, count, seed)
    return _blocked_contains(bits, h1, h2, valid, k, m)


@functools.partial(jax.jit, static_argnames=("k", "m", "seed"))
def blocked_bloom_contains_count_packed(bits, packed, count, k: int, m: int,
                                        seed: int = 0):
    h1, h2, valid = _packed_hashes(packed, count, seed)
    res = _blocked_contains(bits, h1, h2, valid, k, m)
    # graftlint: allow-int-reduce(summing a 0/1 mask over one batch; batches cap at MAX_BUCKET 2^21 << 2^31)
    return jnp.sum(res.astype(jnp.int32))


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("k", "m", "seed")
)
def blocked_bloom_add_bytes(bits, data, lengths, valid, k: int, m: int, seed: int = 0):
    h1, h2 = hashing.murmur3_x64_128(data, lengths, seed)
    return _blocked_add(bits, h1, h2, valid, k, m)


@functools.partial(jax.jit, static_argnames=("k", "m", "seed"))
def blocked_bloom_contains_bytes(bits, data, lengths, valid, k: int, m: int,
                                 seed: int = 0):
    h1, h2 = hashing.murmur3_x64_128(data, lengths, seed)
    return _blocked_contains(bits, h1, h2, valid, k, m)


@functools.partial(jax.jit, static_argnames=("k", "m", "seed"))
def bloom_contains_count_packed(bits, packed, count, k: int, m: int, seed: int = 0):
    """Membership COUNT of a packed batch — a server-side reduce in the
    reference's sense (BITCOUNT-style): only a 4-byte scalar leaves the
    device, which is what makes the FPR@1B probe feasible on a slow link."""
    h1, h2, valid = _packed_hashes(packed, count, seed)
    # graftlint: allow-int-reduce(summing a 0/1 mask over one batch; batches cap at MAX_BUCKET 2^21 << 2^31)
    return jnp.sum(_bloom_contains(bits, h1, h2, valid, k, m).astype(jnp.int32))
