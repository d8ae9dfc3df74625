"""Pallas TPU kernels for the hot sketch ops.

The reference executes its probabilistic ops remotely inside redis-server
(`RedisCommands.java:163-165` PFADD/PFCOUNT/PFMERGE; `RedissonBitSet.java`
BITOP/BITCOUNT); here the same ops are on-chip kernels. These kernels
hand-schedule the bank-sized passes so they stream through VMEM in one
pass regardless of bank size:

* `merge_stack` — PFMERGE over an [S, 16384] sketch bank. Measured at
  parity with XLA's reduce on v5e for 1K sketches (both ~25 us, HBM
  bound); its value is the explicit VMEM blocking, which holds for banks
  far larger than one XLA fusion (the 4K-sketch streaming config) and
  composes with `hll.count` into a single dispatch.
* `popcount_cells` / `bitop_cells` — BITCOUNT / BITOP over the unpacked
  one-uint8-cell-per-bit device layout (`ops/bitset.py`), gridded so
  arbitrarily long bit arrays stream block-by-block.
* `delta_merge` — the delta-ingest retire kernel: one fused elementwise
  max over a [T, L] uint8 stack of host-folded per-target delta planes
  vs their current device state, with a per-row changed flag. OR == max
  in the unpacked cell domain, so one kernel serves hll_add, bloom_add
  and bitset_set deltas in a single launch per pipeline window.

All kernels run in interpreter mode off-TPU (CPU tests) and compiled on
TPU; `engine` gates them on the backend platform. The HLL insert fold
has two device paths: the XLA combining max-scatter
(`hll.insert_scatter`, ~30 us per 1M-key batch on v5e) and the Pallas
segmented-scatter in `redisson_tpu.ingest.kernels` (sort + VMEM-tiled
segment-max), which only a forced `ingest="segment"` selects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_pallas() -> bool:
    """Engine gate: compiled kernels on TPU, XLA elsewhere (tests use the
    kernels directly in interpret mode; prod CPU paths stay on XLA)."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# merge_stack: PFMERGE over [S, m] int32 sketch bank -> [m]
# ---------------------------------------------------------------------------


def _merge_kernel(stack_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] = jnp.maximum(out_ref[:], jnp.max(stack_ref[:], axis=0))


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("block", "interpret"))
def merge_stack(stack: jnp.ndarray, block: int = 64,
                interpret=None) -> jnp.ndarray:
    """Elementwise max over the leading axis of an [S, m] int32 bank.

    Streams `block` sketches per grid step through VMEM (block * m * 4
    bytes; 64 * 64 KB = 4 MB) with a VMEM-resident [m] accumulator.
    Registers are >= 0 so zero-padding the ragged tail is a no-op. The
    stack is a per-call temporary (callers jnp.stack it), so it donates —
    a bank-sized reduce must not hold two bank-sized buffers live.
    """
    s, m = stack.shape
    if s == 0:
        return jnp.zeros((m,), stack.dtype)
    pad = (-s) % block
    if pad:
        stack = jnp.concatenate(
            [stack, jnp.zeros((pad, m), stack.dtype)], axis=0
        )
    grid = (stack.shape[0] // block,)
    return pl.pallas_call(
        _merge_kernel,
        out_shape=jax.ShapeDtypeStruct((m,), stack.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, m), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((m,), lambda i: (0,), memory_space=pltpu.VMEM),
        interpret=_interpret() if interpret is None else interpret,
    )(stack)


# ---------------------------------------------------------------------------
# delta_merge: fused multi-target delta merge over [T, L] uint8 cell stacks
# ---------------------------------------------------------------------------


def _delta_merge_kernel(old_ref, delta_ref, out_ref, changed_ref):
    j = pl.program_id(1)
    old = old_ref[:].astype(jnp.int32)
    merged = jnp.maximum(old, delta_ref[:].astype(jnp.int32))
    out_ref[:] = merged.astype(out_ref.dtype)
    # OR this cell block's per-row "merge changed something" flag into
    # the [rows, 1] accumulator; the first block of a row tile resets it.
    diff = jnp.max((merged != old).astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _():
        changed_ref[:] = diff

    @pl.when(j > 0)
    def _():
        changed_ref[:] = changed_ref[:] | diff


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("block", "interpret"))
def delta_merge(old: jnp.ndarray, delta: jnp.ndarray, block: int = 1 << 13,
                interpret=None):
    """The delta-ingest retire kernel: elementwise max of two [T, L] uint8
    stacks (one row per target; OR == max in the unpacked 0/1 cell domain,
    HLL registers fit uint8) plus a per-row changed flag.

    Streams a (rows, `block`) tile per grid step; the cell axis is the
    inner grid axis, so each row tile's [rows, 1] changed accumulator
    resets at `j == 0` and stays resident across its cell blocks.
    Purely elementwise — bandwidth-bound, no scatter issue port in sight.
    `old` donates AND aliases the merged output, so the merge lands in
    place: peak HBM is one [T, L] stack plus the delta, never two copies
    of the old state (the memstat ledger test pins this).
    Returns (merged [T, L], changed [T] bool)."""
    t, l = old.shape
    # Rows per grid step: the whole stack when T <= 32, else 32 (uint8
    # tiles are (32, 128); callers pad T to a power of two).
    if t > 32 and t % 32:
        raise ValueError(f"{t} rows: pad the stack to a multiple of 32")
    rows = min(t, 32)
    block = min(block, l)
    # Callers pad L to a power of two >= 1024, so block divides l.
    merged, changed = pl.pallas_call(
        _delta_merge_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((t, l), old.dtype),
            jax.ShapeDtypeStruct((t, 1), jnp.int32),
        ),
        grid=(t // rows, l // block),
        in_specs=[
            pl.BlockSpec((rows, block), lambda i, j: (i, j)),
            pl.BlockSpec((rows, block), lambda i, j: (i, j)),
        ],
        out_specs=(
            pl.BlockSpec((rows, block), lambda i, j: (i, j)),
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
        ),
        input_output_aliases={0: 0},
        interpret=_interpret() if interpret is None else interpret,
    )(old, delta)
    return merged, changed[:, 0] != 0


# ---------------------------------------------------------------------------
# popcount_cells: BITCOUNT over unpacked uint8 cells -> scalar
# ---------------------------------------------------------------------------


def _popcount_kernel(cells_ref, out_ref):
    # Per-block partial sums; each block holds <= `block` cells of value
    # 0/1, so an int32 partial cannot overflow for any practical block.
    # The scalar is broadcast over one lane-dense (1, 128) row per step.
    # graftlint: allow-int-reduce(per-block partial over <= `block` 0/1 cells; the 64-bit combine is host-side)
    total = jnp.sum(cells_ref[:].astype(jnp.int32))
    out_ref[:] = jnp.full(out_ref.shape, total, jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def popcount_partials(cells: jnp.ndarray, block: int = 1 << 18,
                      interpret=None) -> jnp.ndarray:
    """Per-block int32 set-bit partials over the unpacked cell layout.

    Each partial counts <= `block` 0/1 cells so int32 cannot overflow;
    callers needing the total past 2^31 set bits combine the [G, 1]
    partials host-side in 64 bits (`ops/bitset.combine_partials` — the
    engine's BITCOUNT path does exactly that).
    """
    n = cells.shape[0]
    if n == 0:
        return jnp.zeros((1, 1), jnp.int32)
    pad = (-n) % block
    if pad:
        cells = jnp.concatenate([cells, jnp.zeros((pad,), cells.dtype)])
    grid_n = cells.shape[0] // block
    out = pl.pallas_call(
        _popcount_kernel,
        out_shape=jax.ShapeDtypeStruct((grid_n, 1, 128), jnp.int32),
        grid=(grid_n,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
        interpret=_interpret() if interpret is None else interpret,
    )(cells)
    return out[:, :, 0]


@functools.partial(jax.jit, static_argnames=("block",))
def popcount_cells(cells: jnp.ndarray, block: int = 1 << 18) -> jnp.ndarray:
    """BITCOUNT as one device scalar — int32, exact under 2^31 set bits
    (use `popcount_partials` + a host combine beyond that)."""
    # graftlint: allow-int-reduce(documented int32 cap: exact under 2^31 set bits per this docstring)
    return jnp.sum(popcount_partials(cells, block))


# ---------------------------------------------------------------------------
# bitop_cells: BITOP AND|OR|XOR over a [K, n] cell stack -> [n]
# ---------------------------------------------------------------------------

_BITOPS = {"and": jnp.bitwise_and, "or": jnp.bitwise_or, "xor": jnp.bitwise_xor}


def _bitop_kernel(op, stack_ref, out_ref):
    fn = _BITOPS[op]
    acc = stack_ref[0]
    for k in range(1, stack_ref.shape[0]):
        acc = fn(acc, stack_ref[k])
    out_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("op", "block", "interpret"))
def bitop_cells(stack: jnp.ndarray, op: str, block: int = 1 << 18,
                interpret=None) -> jnp.ndarray:
    """BITOP over K unpacked-cell operands stacked as [K, n] uint8.

    Grid over n so arbitrarily long bit arrays stream through VMEM; K is
    small (operand count), unrolled inside the kernel.
    """
    k, n = stack.shape
    if n == 0 or k == 0:
        return jnp.zeros((n,), stack.dtype)
    pad = (-n) % block
    if pad:
        stack = jnp.pad(stack, ((0, 0), (0, pad)))
    grid = (stack.shape[1] // block,)
    out = pl.pallas_call(
        functools.partial(_bitop_kernel, op),
        out_shape=jax.ShapeDtypeStruct((stack.shape[1],), stack.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block), lambda i: (0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,), memory_space=pltpu.VMEM),
        interpret=_interpret() if interpret is None else interpret,
    )(stack)
    return out[:n]
