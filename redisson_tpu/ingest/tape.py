"""Window command tape: host encode for the window megakernel.

Redisson's ``CommandBatchService`` (command/CommandBatchService.java)
encodes a whole client batch into one wire flush; this module is the
same move aimed at the TPU dispatch port. It takes EVERY folded delta
plane of a pipeline window — mixed ``hll_add`` / ``bloom_add`` /
``bitset_set``, many targets — and lays them out as one flat command
tape the ``ops/window_kernel`` megakernel consumes in a single launch:

* ``table`` int32 ``[T2, 4]``: ``(op_code, target_row, offset,
  length)`` per arena row. ``target_row`` is the HLL bank row for HLL
  entries (-1 for store-backed entries — the host keeps the row ->
  object map); ``offset`` is the row's byte offset into the flattened
  wire buffer; ``length`` the valid cell count.
* ``wire`` uint8 ``[T2, W]``: one operand segment per row — dense
  register bytes for HLL entries, packed big-endian bits for bloom /
  bitset. Sparse planes are re-materialized into their segment here
  (the tape trades the sparse link encoding for the single launch; the
  planner arbitrates that trade, see ``ingest/planner.py``).

Rows are ordered HLL-first so the device side can gather/scatter the
bank rows as one contiguous prefix; ``T2`` and ``W`` are padded to
powers of two (shape-stable dispatch, G003) with ``OP_PAD`` identity
rows (length 0 merges as a zero delta under max).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List

import numpy as np

from redisson_tpu.ingest.delta import DeltaPlane
from redisson_tpu.ops.window_kernel import (
    OP_BITSET, OP_BLOOM, OP_HLL, OP_PAD, TABLE_COLS)

_OP_OF = {"hll_add": OP_HLL, "bloom_add": OP_BLOOM, "bitset_set": OP_BITSET}

#: Minimum cell-lane count — matches engine.MIN_BUCKET so tape arenas
#: reuse the same pow2 size classes (and jit cache entries) as the delta
#: path. Kept as a literal: this module is numpy-only, no jax import.
MIN_LANES = 1 << 10

#: Minimum wire width in bytes (one packed lane group).
MIN_WIRE = 128


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


@dataclass
class WindowTape:
    """One encoded pipeline window, ready for a single fused launch."""

    table: np.ndarray               # int32 [T2, TABLE_COLS]
    wire: np.ndarray                # uint8 [T2, W]
    lanes: int                      # padded cell-lane count L
    n_hll: int                      # HLL entries (arena rows 0..n_hll-1)
    hll_rows: np.ndarray            # int32 [n_hll] bank rows
    planes: List[DeltaPlane] = field(default_factory=list)  # arena order
    link_bytes: int = 0             # table + wire bytes shipped

    @property
    def n_entries(self) -> int:
        return len(self.planes)


def _wire_row(p: DeltaPlane) -> np.ndarray:
    """A plane's operand segment: the folded byte plane, re-densified
    from the sparse pair encoding when needed (indices are unique — they
    come from flatnonzero of the folded plane — so plain assign is the
    exact inverse of the sparse encode)."""
    if not p.sparse:
        return p.dense
    seg = np.zeros((p.plane_bytes,), np.uint8)
    if p.nnz:
        seg[p.idx[: p.nnz]] = p.val[: p.nnz]
    return seg


def encode_window(planes: List[DeltaPlane],
                  hll_row: Callable[[str], int]) -> WindowTape:
    """Encode a window's folded planes into one command tape.

    ``hll_row`` maps an hll_add target name to its bank row (the caller
    owns target->row placement). Raises ValueError on a kind the tape has
    no op code for — eligibility is the caller's job.
    """
    ordered = ([p for p in planes if p.kind == "hll_add"]
               + [p for p in planes if p.kind != "hll_add"])
    if len(ordered) != len(planes):
        raise ValueError("tape: unordered plane list changed size")
    n = len(ordered)
    n_hll = sum(1 for p in ordered if p.kind == "hll_add")
    t2 = _pow2(max(n, 1))
    lanes = max(MIN_LANES, _pow2(max((p.cells for p in ordered), default=1)))
    width = max(MIN_WIRE,
                _pow2(max((p.plane_bytes for p in ordered), default=1)))
    table = np.zeros((t2, TABLE_COLS), np.int32)
    table[:, 0] = OP_PAD
    table[:, 1] = -1
    wire = np.zeros((t2, width), np.uint8)
    rows = np.zeros((n_hll,), np.int32)
    for i, p in enumerate(ordered):
        try:
            op = _OP_OF[p.kind]
        except KeyError:
            raise ValueError(f"tape: no op code for kind {p.kind!r}")
        row = hll_row(p.target) if op == OP_HLL else -1
        if op == OP_HLL:
            rows[i] = row
        table[i] = (op, row, i * width, p.cells)
    for i, p in enumerate(ordered):
        wire[i, : p.plane_bytes] = _wire_row(p)
    return WindowTape(
        table=table, wire=wire, lanes=lanes, n_hll=n_hll, hll_rows=rows,
        planes=list(ordered),
        link_bytes=int(table.nbytes) + int(wire.nbytes))
