"""Segmented-scatter insert kernels (Pallas TPU) + XLA fallbacks.

The naive insert path is XLA's combining scatter
(`hll.insert_scatter`: `registers.at[bucket].max(rank)`), which lowers
to a serialized scatter loop and measured 6.5% of its own scatter-issue
roofline in round 5.  The segmented formulation turns the random
scatter into a streaming pass:

  1. encode each update as `code = bucket << 6 | rank` (HLL) or
     `code = cell_index` (bit structures) and sort ascending — XLA's
     bitonic sort, outside the kernel;
  2. compute, per register tile of size T, the span [start, end) of
     sorted codes that land in the tile (`searchsorted` on the tile
     boundaries) and hand the spans to the kernel as scalar prefetch;
  3. grid over the m/T tiles: each grid step loops over its span in
     chunks of C codes, dense-expands each chunk against the tile
     (`local == iota` compare, a (C, T) VPU op), and folds
     segment-max (HLL rank) / segment-or (bit cells) into a VMEM
     accumulator — no scatter instruction anywhere;
  4. `out = max(registers, acc)` per tile.

Total work is O(N * T / C_vpu + m): every code is touched by exactly
one tile (codes outside the tile's span are never loaded; codes from a
neighbouring tile that stray into a chunk's tail self-exclude because
their `local` index falls outside [0, T)).  Sorted codes sit fully in
VMEM (the engine caps batches at 2^21 keys = 8 MB of int32).

Both kernels run in interpreter mode off-TPU for tests; the
`segmented_*` convenience wrappers gate on `use_pallas()` and fall
back to the XLA `*_lax` variants (sort + run-compress + small scatter,
the same shape as `hll.insert_sorted`) so CPU callers never pay
interpreter overhead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from redisson_tpu.ops import hll
from redisson_tpu.ops.pallas_kernels import _interpret, use_pallas

# Sentinel code padded past the real batch: sorts to the end and its
# bucket (sentinel >> shift) is >= any register count, so no tile ever
# matches it in the dense-expand compare.
_SENTINEL = jnp.iinfo(jnp.int32).max


#: Sorted codes are loaded in chunks of this many, each starting on a
#: multiple of it: 1-D int32 arrays are tiled by 1024 on the TPU, and
#: Mosaic must prove every dynamic load aligned.
_CHUNK = 1024

#: Largest (codes x tile) dense-expand compare per inner step.
_EXPAND_CELLS = 1 << 17

#: The tile spans ride in SMEM (1 MiB on v5e); keep them well under it.
_MAX_TILES = 1 << 16


def _seg_kernel(sub: int, shift: int, starts_ref, codes_ref, regs_ref,
                out_ref):
    t = pl.program_id(0)
    tile = out_ref.shape[0]
    base = t * tile
    # Chunks start on a multiple of _CHUNK; codes of the previous tile
    # that the rounded start pulls in self-exclude like the tail's strays.
    start = (starts_ref[t] // _CHUNK) * _CHUNK
    span = starts_ref[t + 1] - start
    nchunks = (span + _CHUNK - 1) // _CHUNK

    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, tile), 1)

    def body(k, acc):
        # Chunk loads may run into the next tile's codes or the sentinel
        # pad; both have local indices outside [0, tile) and contribute
        # nothing to the compare below.
        c = codes_ref[pl.ds(pl.multiple_of(start + k * _CHUNK, _CHUNK),
                            _CHUNK)]
        for s in range(_CHUNK // sub):
            cs = c[s * sub:(s + 1) * sub]
            if shift:
                bucket = jax.lax.shift_right_logical(cs, shift)
                val = jnp.bitwise_and(cs, (1 << shift) - 1)
            else:
                bucket = cs
                val = jnp.ones_like(cs)
            local = bucket - base
            eq = local[:, None] == lane  # (sub, tile) dense expand
            contrib = jnp.where(eq, val[:, None], 0)
            acc = jnp.maximum(acc, jnp.max(contrib, axis=0))
        return acc

    acc = jax.lax.fori_loop(
        0, nchunks, body, jnp.zeros((tile,), jnp.int32)
    )
    out_ref[:] = jnp.maximum(regs_ref[:].astype(jnp.int32), acc).astype(
        out_ref.dtype
    )


def _segmented_call(registers, codes, shift, tile, interpret):
    """Shared driver: sort codes, compute tile spans, launch the grid."""
    m = registers.shape[0]
    while -(-m // tile) > _MAX_TILES:
        tile *= 2
    mpad = (-m) % tile
    if mpad:
        registers = jnp.concatenate(
            [registers, jnp.zeros((mpad,), registers.dtype)]
        )
    g = registers.shape[0] // tile

    codes = jnp.sort(codes)
    # Sentinels fill the codes to a _CHUNK multiple plus one spare chunk,
    # so every aligned pl.ds slice stays in bounds.
    n = codes.shape[0]
    npad = -(-n // _CHUNK) * _CHUNK + _CHUNK
    codes = jnp.concatenate(
        [codes, jnp.full((npad - n,), _SENTINEL, jnp.int32)]
    )
    boundaries = (jnp.arange(g + 1, dtype=jnp.int32) * tile) << shift
    starts = jnp.searchsorted(codes, boundaries).astype(jnp.int32)
    out = segment_fold(registers, codes, starts, shift=shift, tile=tile,
                       interpret=interpret)
    return out[:m] if mpad else out


def segment_fold(registers, codes, starts, *, shift: int, tile: int,
                 interpret=None):
    """The kernel launch: fold sorted, sentinel-padded `codes` into the
    `registers` tiles whose spans `starts` [m / tile + 1] holds."""
    npad = codes.shape[0]
    sub = max(8, min(_CHUNK, _EXPAND_CELLS // tile))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(registers.shape[0] // tile,),
        in_specs=[
            pl.BlockSpec((npad,), lambda i, starts: (0,)),
            pl.BlockSpec((tile,), lambda i, starts: (i,)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda i, starts: (i,)),
    )
    # The whole sorted-code array is one VMEM block, double-buffered:
    # 16 MB at 2^21 codes, past v5e's default scoped VMEM limit.
    vmem = 2 * npad * 4 + 4 * tile * registers.dtype.itemsize + (16 << 20)
    return pl.pallas_call(
        functools.partial(_seg_kernel, sub, shift),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(registers.shape, registers.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=_interpret() if interpret is None else interpret,
    )(starts, codes, registers)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def hll_insert_segmented(registers, bucket, rank, *, tile: int = 1024,
                         interpret=None):
    """Segment-max fold of a (bucket, rank) batch into [m] HLL registers.

    `tile` registers per grid step (a multiple of 1024, the TPU's 1-D
    int32 tiling; m is padded to a multiple).
    """
    if bucket.shape[0] == 0:
        return registers
    codes = bucket.astype(jnp.int32) * 64 + rank.astype(jnp.int32)
    return _segmented_call(registers, codes, 6, tile, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def bits_insert_segmented(cells, idx, *, tile: int = 4096, interpret=None):
    """Segment-or: set `cells[idx] = 1` over the unpacked uint8 layout.

    Codes are the raw cell indices (shift=0, value 1); the accumulator's
    max over {0, 1} is the or. `tile` is a multiple of 4096, the 1-D
    uint8 tiling, and doubles until the tile spans fit SMEM.
    """
    if idx.shape[0] == 0:
        return cells
    return _segmented_call(cells, idx.astype(jnp.int32), 0, tile, interpret)


# ---------------------------------------------------------------------------
# XLA fallbacks — identical semantics, no Pallas (prod CPU path)
# ---------------------------------------------------------------------------


def hll_insert_segmented_lax(registers, bucket, rank):
    """Sort + run-compress + scatter of the <= min(N, m) survivors —
    the same batch compression the kernel does, expressed in XLA."""
    return hll.insert_sorted(registers, bucket, rank)


def bits_insert_segmented_lax(cells, idx):
    """Sorted-dedup set: sort indices, scatter 1 at each (duplicates
    collapse naturally under `.set`; sorting keeps the memory access
    pattern streaming like the kernel's)."""
    cells = jnp.asarray(cells)
    s = jnp.sort(jnp.asarray(idx).astype(jnp.int32))
    return cells.at[s].set(jnp.ones_like(s, cells.dtype), mode="drop")


# ---------------------------------------------------------------------------
# Gated entry points (what the engine/backends call)
# ---------------------------------------------------------------------------


def segmented_hll_add(registers, bucket, rank):
    """Pallas segmented insert on TPU, XLA sort-compress elsewhere."""
    if use_pallas():
        return hll_insert_segmented(registers, bucket, rank)
    return hll_insert_segmented_lax(registers, bucket, rank)


def segmented_bits_set(cells, idx):
    if use_pallas():
        return bits_insert_segmented(cells, idx)
    return bits_insert_segmented_lax(cells, idx)
