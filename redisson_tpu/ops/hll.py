"""HyperLogLog kernels: insert / count / merge over dense register arrays.

Semantics follow Redis' dense HLL (the reference's `PFADD/PFCOUNT/PFMERGE`
pass-through, `RedissonHyperLogLog.java:40-97` + `RedisCommands.java:163-165`):

  * p = 14 -> m = 16384 registers (Redis' fixed precision);
  * bucket = low p bits of the 64-bit hash;
  * rank   = trailing-zero count of (hash >> p) | 2^q  plus one, q = 64 - p,
    so rank in [1, q+1] (Redis `hllPatLen`).

Redis hashes with MurmurHash64A; we hash with MurmurHash3 x64 128 (north-star
spec) and use its low half — same family, same uniformity, so the error
envelope is identical even though individual sketches are not byte-compatible
with a Redis server's (import/export converts via raw register values).

Cardinality estimation uses the Ertl estimator (tau/sigma refinement, "New
cardinality estimation algorithms for HyperLogLog sketches", 2017): no
empirical bias tables, relative error ~1.04/sqrt(m) = 0.81% at p=14, well
inside the <2% target, and branch-free enough to run under jit.

Registers are int32 on device (values 0..51): scatter-max and histograms
vectorize better on 32-bit lanes than uint8, and 16384*4 bytes is nothing.

Insert offers two aggregation strategies (see `add_batch`):
  * 'scatter' — registers.at[bucket].max(rank): XLA's combining scatter.
    The default; its rate on this round's chip is not measured yet
    (bench.py bench_kernel times it in a device-resident loop).
  * 'sort'    — encode bucket*64+rank, sort, keep run maxima, scatter only
    the <= m unique survivors. XLA's 1-D sort lowers to a bitonic network
    on TPU; ~2x slower than scatter at 1M-key batches — a
    fallback/debugging aid.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from redisson_tpu.ops import u64 as u
from redisson_tpu.ops.u64 import U64

P = 14
M = 1 << P  # 16384 registers
Q = 64 - P  # 50
MAX_RANK = Q + 1  # 51


def make(m: int = M) -> jnp.ndarray:
    """Fresh (empty) register array."""
    return jnp.zeros((m,), jnp.int32)


def bucket_rank(h: U64, p: int = P):
    """Split a 64-bit hash into (bucket, rank) per Redis hllPatLen."""
    m = 1 << p
    q = 64 - p
    bucket = (h.lo & (m - 1)).astype(jnp.int32)
    rest = u.shr(h, p)
    rest = u.or_(rest, u.shl(u.full(jnp.shape(h.lo), 1), q))
    rank = u.ctz(rest) + 1
    return bucket, rank.astype(jnp.int32)


def insert_scatter(registers: jnp.ndarray, bucket: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    return registers.at[bucket].max(rank)


def insert_sorted(registers: jnp.ndarray, bucket: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    """Sort-compress the batch before touching the registers.

    Encode each update as bucket*64+rank, sort ascending, and keep only each
    bucket's run maximum (the last element of its run). The final scatter has
    at most min(N, m) effective updates instead of N.
    """
    combined = bucket * 64 + rank
    s = jnp.sort(combined)
    is_last = jnp.concatenate([s[1:] // 64 != s[:-1] // 64, jnp.ones((1,), bool)])
    # Route non-survivors to a dump row so the scatter stays shape-static.
    b = jnp.where(is_last, s // 64, registers.shape[0])
    r = jnp.where(is_last, s % 64, 0)
    return jnp.concatenate([registers, jnp.zeros((1,), jnp.int32)]).at[b].max(
        r, mode="drop"
    )[:-1]


def add_hashes(
    registers: jnp.ndarray,
    h: U64,
    impl: Literal["scatter", "sort"] = "scatter",
) -> jnp.ndarray:
    """Fold a batch of 64-bit hashes into the registers."""
    p = _p_of(registers.shape[0])
    bucket, rank = bucket_rank(h, p)
    if impl == "scatter":
        return insert_scatter(registers, bucket, rank)
    return insert_sorted(registers, bucket, rank)


def _p_of(m: int) -> int:
    p = int(m).bit_length() - 1
    if (1 << p) != m:
        raise ValueError(f"register count {m} is not a power of two")
    return p


def merge(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """PFMERGE of two sketches = elementwise register max."""
    return jnp.maximum(a, b)


def merge_many(stack: jnp.ndarray) -> jnp.ndarray:
    """PFMERGE of [S, m] stacked sketches."""
    return jnp.max(stack, axis=0)


# ---------------------------------------------------------------------------
# Cardinality estimation (Ertl 2017, improved raw estimator)
# ---------------------------------------------------------------------------

_ITERS = 48  # fixed-point iterations; f32 converges in < 30

# Implementation notes:
#   * Unrolled python loops, NOT lax.fori_loop: a sequential scalar loop
#     body pays per-iteration overhead that a fixed unrolled chain does not.
#   * The chains run ELEMENTWISE over the whole [q+2] histogram vector and
#     lanes are selected at the end; keep estimator math vector-shaped
#     until the final reduce.


def _sigma(x):
    """sigma(x) = x + sum_{k>=1} x^(2^k) * 2^(k-1); diverges at x=1.
    Elementwise over any shape."""
    x = x.astype(jnp.float32)
    y = jnp.float32(1.0)
    z = x
    for _ in range(_ITERS):
        x = x * x
        z = z + x * y
        y = y * 2.0
    return z


def _tau(x):
    x = x.astype(jnp.float32)
    y = jnp.float32(1.0)
    z = 1.0 - x
    for _ in range(_ITERS):
        x = jnp.sqrt(x)
        y = y * 0.5
        z = z - jnp.square(1.0 - x) * y
    return z / 3.0


def count(registers: jnp.ndarray) -> jnp.ndarray:
    """Cardinality estimate (float32 scalar; 0 for an empty sketch).

    Ertl's z accumulator is computed as one weighted reduce instead of the
    sequential halving loop: unrolling `z = 0.5*(z + hist[k])` q times
    assigns hist[k] the weight 2^-k and the tau term 2^-q, so
    z = 2^-q*m*tau + sum_k 2^-k*hist[k] + m*sigma — mathematically
    identical, vector-shaped end to end (see the chain-shape note above
    _sigma), and one VPU pass instead of 50 dependent scalar steps."""
    m = registers.shape[0]
    p = _p_of(m)
    q = 64 - p
    # Histogram of register values 0..q+1.
    hist = jnp.zeros((q + 2,), jnp.float32).at[registers].add(1.0)
    mf = jnp.float32(m)
    x = hist / mf  # [q+2]
    sig = _sigma(x)  # elementwise; only lane 0 is used
    tau = _tau(1.0 - x)  # elementwise; only lane q+1 is used
    lane = jnp.arange(q + 2)
    w = jnp.where((lane >= 1) & (lane <= q),
                  jnp.exp2(-lane.astype(jnp.float32)), 0.0)
    combo = (hist * w
             + jnp.where(lane == q + 1,
                         mf * jnp.exp2(jnp.float32(-q)) * tau, 0.0)
             + jnp.where(lane == 0, mf * sig, 0.0))
    z = jnp.sum(combo)
    alpha_inf = jnp.float32(0.5 / jnp.log(2.0))
    est = alpha_inf * mf * mf / z
    # Load-bearing: with the fixed iteration count sigma(1) is a finite
    # ~2^47 partial sum, so an empty sketch would estimate small-but-nonzero
    # without this guard.
    return jnp.where(jnp.all(registers == 0), jnp.float32(0.0), est)


@jax.jit
def count_jit(registers):
    return count(registers)


@functools.partial(jax.jit, static_argnames=("impl",))
def add_hashes_jit(registers, h, impl: str = "scatter"):
    return add_hashes(registers, h, impl)


@jax.jit
def merge_jit(a, b):
    return merge(a, b)
