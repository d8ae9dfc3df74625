"""The window megakernel: one fused launch per pipeline window.

``bench.py`` pins the ingest ceiling at scatter-ISSUE, not HBM bandwidth
(~6% of roofline, ``binding=scatter-issue``): per-launch dispatch
overhead dominates once the delta path has already collapsed link bytes.
This kernel attacks the launch count itself, modeled on the FPGA HLL
accelerator's pre-aggregation pipeline (PAPERS.md, arxiv 2005.13332) and
Redisson's ``CommandBatchService`` single-flush encode: the host encodes
an ENTIRE pipeline window — mixed hll_add / bloom_add / bitset_set, many
targets — into a flat **command tape** (``ingest/tape.py``), and this
kernel consumes the whole tape in a single grid-iterated launch.

Tape layout (one arena row per folded delta plane):

* ``table`` — int32 ``[T, TABLE_COLS]`` rows ``(op_code, target_row,
  offset, length, shard)``.  ``op_code`` selects the merge semantics per
  entry (``OP_HLL``: register max-merge on dense uint8 registers;
  ``OP_BLOOM`` / ``OP_BITSET``: bit-OR on a packed big-endian bit plane);
  ``target_row`` is the HLL bank row (-1 for store-backed rows — the host
  keeps the arena-row -> store-object map); ``offset`` is the entry's
  byte offset into the flat wire buffer; ``length`` is its valid cell
  count; ``shard`` is the logical cluster shard the entry belongs to
  (column ``COL_SHARD`` — the tape's shard axis: a mesh data-plane
  window mixes entries from many logical shards and still retires in
  ONE launch; the kernel itself merges by ``op_code``/``length`` only,
  the shard column carries attribution through the fused dispatch).
* ``wire`` — uint8 ``[T, W]`` operand buffer, one row per entry: dense
  register bytes for HLL rows, packed bits for bloom/bitset rows.
* ``old`` — uint8 ``[T, L]`` the matching current-state rows
  (bank-resident HLL rows gathered as uint8 + store cell arrays),
  donated so the merge lands in place — no copy-in/copy-out per target.

The kernel grid-iterates ``(entry-tile, cell-block)``; each step reads
its entries' ``op_code`` and ``length`` from the table tile to decode
their wire block — raw bytes for dense entries, an unpack-by-shift for
packed entries — and max-merges into the old rows (OR == max in the 0/1
cell domain; HLL registers are 0..64).  A per-row flag accumulates
``changed`` (the PFADD result bit).  Off-TPU the lax fallback computes
the identical function (bit-for-bit — tests pin it), so CPU CI and the
TPU kernel share one contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from redisson_tpu.ops import bitset
from redisson_tpu.ops.pallas_kernels import (accumulate_changed, row_block,
                                             use_pallas)

# Tape op codes (table column 0). PAD rows carry length == 0 and merge as
# identity (zero delta under max).
OP_PAD = 0
OP_HLL = 1      # dense uint8 register plane, elementwise max
OP_BLOOM = 2    # packed big-endian bit plane, bit-OR
OP_BITSET = 3   # packed big-endian bit plane, bit-OR (old bits read back)

# Table geometry: (op_code, target_row, offset, length, shard). The shard
# column rides along for multi-shard windows (mesh data plane); both the
# Pallas kernel and the lax fallback read only op_code and length, so the
# merge function is invariant to it by construction.
TABLE_COLS = 5
COL_SHARD = 4

#: op codes whose wire segment is already in the cell domain (one byte
#: per cell); everything else is a packed bit plane the kernel unpacks.
DENSE_OPS = (OP_HLL,)

_DEFAULT_BLOCK = 1 << 11


def _window_tape_kernel(tab_ref, old_ref, dense_ref, packed_ref,
                        out_ref, changed_ref):
    """One grid step: a (rows, block) tile of entries x cells — decode
    each entry's wire block per its op_code and max-merge into the old
    rows."""
    j = pl.program_id(1)
    rows, block = out_ref.shape
    tab = tab_ref[:]
    op_code = tab[:, 0:1]
    length = tab[:, 3:4]
    old = old_ref[:].astype(jnp.int32)
    dense = dense_ref[:].astype(jnp.int32)
    # Packed decode: cell c lives in wire byte c >> 3 at bit 7 - (c & 7)
    # (numpy packbits order — matches engine.bitset_pack/delta_unpack).
    # jnp.repeat is the element repeat ([a x8, b x8, ...]); pltpu.repeat
    # is a tile and would decode the wrong bytes.
    rep8 = jnp.repeat(packed_ref[:].astype(jnp.int32), 8, axis=1)
    pos = j * block + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    unpacked = (rep8 >> (7 - (pos & 7))) & 1
    delta = jnp.where(op_code == OP_HLL, dense, unpacked)
    delta = jnp.where(pos < length, delta, 0)
    merged = jnp.maximum(old, delta)
    out_ref[:] = merged.astype(out_ref.dtype)
    accumulate_changed(changed_ref, j, merged, old)


def _normalize(old, wire, block):
    """Shared precondition handling: the wire buffer widens to the lane
    count (its width is the max SEGMENT bytes, always <= max cells) so
    dense reads never clamp, and the block divides the pow2 lane count."""
    t2, lanes = old.shape
    w = wire.shape[1]
    if w < lanes:
        wire = jnp.pad(wire, ((0, 0), (0, lanes - w)))
    block = min(block, lanes)
    return wire, block


def window_merge_pallas(old, wire, table, block: int = _DEFAULT_BLOCK,
                        interpret: bool = None):
    """The Pallas window megakernel. `old` [T, L] uint8 aliases the
    merged output (in-place against the donated arena); `wire` is passed
    twice so the same buffer is windowed at cell granularity (dense
    entries) AND byte granularity (packed entries). Lanes are a power of
    two >= 1024, so a packed block of ``block // 8`` bytes stays a
    multiple of 128."""
    if interpret is None:
        interpret = not use_pallas()
    wire, block = _normalize(old, wire, block)
    t2, lanes = old.shape
    rows = row_block(t2)
    merged, changed = pl.pallas_call(
        _window_tape_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((t2, lanes), old.dtype),
            jax.ShapeDtypeStruct((t2, 1), jnp.int32),
        ),
        grid=(t2 // rows, lanes // block),
        in_specs=[
            pl.BlockSpec((rows, table.shape[1]), lambda t, j: (t, 0)),
            pl.BlockSpec((rows, block), lambda t, j: (t, j)),
            pl.BlockSpec((rows, block), lambda t, j: (t, j)),
            pl.BlockSpec((rows, block // 8), lambda t, j: (t, j)),
        ],
        out_specs=(
            pl.BlockSpec((rows, block), lambda t, j: (t, j)),
            pl.BlockSpec((rows, 1), lambda t, j: (t, 0)),
        ),
        input_output_aliases={1: 0},  # old -> merged, in place
        interpret=interpret,
    )(table, old, wire, wire)
    return merged, changed[:, 0] != 0


def window_merge_lax(old, wire, table, block: int = _DEFAULT_BLOCK):
    """Bit-identical lax fallback (the CPU/CI path): same tape contract,
    same decode, one XLA fusion instead of the Pallas grid."""
    wire, _ = _normalize(old, wire, block)
    t2, lanes = old.shape
    op_code = table[:, 0:1]
    length = table[:, 3:4]
    pos = jnp.arange(lanes, dtype=jnp.int32)[None, :]
    unpacked = bitset.unpack(wire[:, : lanes // 8].reshape(-1),
                             t2 * lanes).reshape(t2, lanes)
    delta = jnp.where(op_code == OP_HLL, wire, unpacked)
    delta = jnp.where(pos < length, delta, 0).astype(old.dtype)
    merged = jnp.maximum(old, delta)
    return merged, jnp.any(merged != old, axis=1)


def window_merge(old, wire, table, block: int = _DEFAULT_BLOCK):
    """Platform gate: compiled megakernel on TPU, lax elsewhere. Both
    return ``(merged [T, L] uint8, changed [T] bool)``."""
    if use_pallas():
        return window_merge_pallas(old, wire, table, block)
    return window_merge_lax(old, wire, table, block)
