"""Tier B: jaxpr audit of the public device ops.

Traces every public op in ``redisson_tpu/ops`` (plus the ingest kernels)
with small representative shapes via ``jax.make_jaxpr`` — no execution —
and walks the jaxpr (including nested pjit/scan/cond sub-jaxprs) for:

* J001 — any int64/uint64/float64 aval. The engine targets TPU without
  jax_enable_x64; a 64-bit dtype in a jaxpr means a silent x64 leak that
  would either crash on TPU or silently truncate.
* J002 — a ``convert_element_type`` that *narrows* an integer whose
  producer (through shape-only ops) is a reduction: the signature of a
  wide accumulation being squeezed into a narrower lane after the fact.
  Registry entries may allow specific target dtypes with a reason
  (e.g. bitset.pack's uint8: an 8-term weighted sum of bits is <= 255
  by construction).
* J000 — the op failed to trace at all.

The audit is registry-driven so every new public op must be added here
(tests/test_static_analysis.py checks registry coverage against the ops
modules' public names).
"""

from __future__ import annotations

from .findings import Finding

#: ops/ public names that are host-side (python ints / bytes) or trivial
#: re-exports — not traceable device ops, deliberately not audited.
HOST_SIDE = {
    "bitset": {"combine_partials", "combine_length", "combine_bitpos",
               "cardinality", "length", "bitpos", "make"},
    "bloom": {"check_size", "blocked_geometry", "optimal_num_of_bits",
              "optimal_num_of_hash_functions", "MAX_SIZE"},
    "bloom_math": {"optimal_num_of_bits", "optimal_num_of_hash_functions",
                   "check_cap", "count_estimate", "MAX_SIZE"},
    "crc16": {"crc16", "hashtag", "key_slot"},
    "hll": {"make"},
    "u64": {"const", "to_python", "full"},
    "hashing": {"REDIS_HLL_SEED"},
    "pallas_kernels": {"use_pallas"},
}

_DTYPES_64 = {"int64", "uint64", "float64"}
_PASSTHROUGH = {"reshape", "squeeze", "transpose", "broadcast_in_dim",
                "slice", "rev", "copy", "expand_dims"}
_REDUCTIONS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
               "cumsum", "dot_general", "argmax", "argmin",
               "reduce_and", "reduce_or"}


def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_jaxprs(sub)


def _sub_jaxprs(v):
    import jax.core as core

    # jax moved Jaxpr/ClosedJaxpr around across versions; duck-type.
    # ClosedJaxpr forwards .eqns, so unwrap .jaxpr FIRST.
    if hasattr(v, "jaxpr"):
        yield v.jaxpr
    elif hasattr(v, "eqns"):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _sub_jaxprs(item)
    del core


def _check_one(name: str, closed, allow_narrow: dict) -> list[Finding]:
    findings: list[Finding] = []
    seen_64: set[str] = set()
    loc = f"<jaxpr:{name}>"
    for jx in _iter_jaxprs(closed.jaxpr):
        producers = {}
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                producers[id(ov)] = eqn
        all_vars = list(jx.constvars) + list(jx.invars) + list(jx.outvars)
        for eqn in jx.eqns:
            all_vars += [v for v in list(eqn.invars) + list(eqn.outvars)
                         if hasattr(v, "aval")]
        for v in all_vars:
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in _DTYPES_64 and dt not in seen_64:
                seen_64.add(dt)
                findings.append(Finding(
                    "J001", loc, 0,
                    f"{dt} appears in the jaxpr of `{name}` — the engine "
                    "runs without jax_enable_x64; 64-bit avals mean a "
                    "silent x64 leak",
                    "keep 64-bit quantities as uint32 (hi, lo) lanes "
                    "(ops/u64) or combine host-side in python ints",
                ))
        for eqn in jx.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0]
            old = getattr(getattr(src, "aval", None), "dtype", None)
            new = eqn.params.get("new_dtype")
            if old is None or new is None:
                continue
            if old.kind not in "iu" or new.kind not in "iu":
                continue
            if new.itemsize >= old.itemsize:
                continue
            prod = producers.get(id(src))
            hops = 0
            while prod is not None and prod.primitive.name in _PASSTHROUGH \
                    and hops < 6:
                src = prod.invars[0]
                prod = producers.get(id(src))
                hops += 1
            if prod is None or prod.primitive.name not in _REDUCTIONS:
                continue
            if str(new) in allow_narrow:
                continue
            findings.append(Finding(
                "J002", loc, 0,
                f"`{name}`: {prod.primitive.name} result ({old}) is "
                f"narrowed to {new} — the accumulator was wider than the "
                "value that survives",
                "reduce in chunks bounded to the narrow dtype's range and "
                "combine host-side, or register an allow_narrow reason in "
                "tools/graftlint/jaxpr_audit.py if the bound is proven",
            ))
    return findings


def build_registry():
    """(name, thunk, allow_narrow) triples. Thunks build (fn, args) lazily
    so importing this module doesn't import jax."""
    import functools

    import jax
    import jax.numpy as jnp

    from redisson_tpu import engine as eng
    from redisson_tpu.ingest import kernels as ik
    from redisson_tpu.ops import bitset, bloom, hashing, hll
    from redisson_tpu.ops import pallas_kernels as pk
    from redisson_tpu.ops import u64 as u
    from redisson_tpu.parallel.mesh import SLOT_AXIS, get_mesh

    bits = jnp.zeros(((1 << 20) + 8,), jnp.uint8)  # exercises the pad path
    small = jnp.zeros((4096,), jnp.uint8)
    idx1d = jnp.zeros((16,), jnp.uint32)
    idx2d = jnp.zeros((8, 5), jnp.int32)
    a64 = u.U64(jnp.arange(8, dtype=jnp.uint32), jnp.arange(8, dtype=jnp.uint32))
    b64 = u.U64(jnp.ones((8,), jnp.uint32), jnp.full((8,), 7, jnp.uint32))
    regs = jnp.zeros((hll.M,), jnp.int32)
    bucket = jnp.zeros((8,), jnp.int32)
    rank = jnp.ones((8,), jnp.int32)
    data = jnp.zeros((8, 24), jnp.uint8)
    lengths = jnp.full((8,), 24, jnp.int32)
    stack = jnp.zeros((3, 2048), jnp.uint8)
    bank = jnp.zeros((100, 128), jnp.int32)
    pred = jnp.zeros((8,), bool)

    m_np2 = 1000003        # non-power-of-two <= 2^31: long-division path
    m_p2 = 1 << 20         # power-of-two: mask path
    pc = functools.partial

    reg = [
        # -- bitset ---------------------------------------------------------
        ("bitset.get_bits", lambda: (bitset.get_bits, (small, idx1d)), {}),
        ("bitset.set_bits", lambda: (bitset.set_bits, (small, idx1d)), {}),
        ("bitset.clear_bits", lambda: (bitset.clear_bits, (small, idx1d)), {}),
        ("bitset.flip_bits", lambda: (bitset.flip_bits, (small, idx1d)), {}),
        ("bitset.set_range",
         lambda: (lambda b: bitset.set_range(b, 3, 1000, True), (small,)), {}),
        ("bitset.set_range(clear,tail)",
         lambda: (lambda b: bitset.set_range(b, 9, 1 << 33, False), (small,)), {}),
        ("bitset.cardinality_partials",
         lambda: (bitset.cardinality_partials, (bits,)), {}),
        ("bitset.length_partials", lambda: (bitset.length_partials, (bits,)), {}),
        ("bitset.bitpos_partials(1)",
         lambda: (pc(bitset.bitpos_partials, value=1), (bits,)), {}),
        ("bitset.bitpos_partials(0)",
         lambda: (pc(bitset.bitpos_partials, value=0), (bits,)), {}),
        ("bitset.bitop_and", lambda: (bitset.bitop_and, (small, small)), {}),
        ("bitset.bitop_or", lambda: (bitset.bitop_or, (small, small)), {}),
        ("bitset.bitop_xor", lambda: (bitset.bitop_xor, (small, small)), {}),
        ("bitset.pack", lambda: (bitset.pack, (jnp.zeros((37,), jnp.uint8),)),
         {"uint8": "8-term weighted sum of 0/1 bits is <= 255 by construction"}),
        ("bitset.unpack",
         lambda: (pc(bitset.unpack, nbits=37), (jnp.zeros((5,), jnp.uint8),)), {}),
        # -- bloom ----------------------------------------------------------
        ("bloom.indexes(np2)",
         lambda: (pc(bloom.indexes, k=5, m=m_np2), (a64, b64)), {}),
        ("bloom.indexes(p2)",
         lambda: (pc(bloom.indexes, k=5, m=m_p2), (a64, b64)), {}),
        ("bloom.add", lambda: (bloom.add, (small, idx2d)), {}),
        ("bloom.contains", lambda: (bloom.contains, (small, idx2d)), {}),
        ("bloom.count_estimate",
         lambda: (pc(bloom.count_estimate, size=m_p2, hash_iterations=5),
                  (jnp.int32(100),)), {}),
        ("bloom.blocked_indexes",
         lambda: (pc(bloom.blocked_indexes, k=5, m=m_p2), (a64, b64)), {}),
        ("bloom.blocked_absolute",
         lambda: (bloom.blocked_absolute, (bucket, idx2d)), {}),
        ("bloom.blocked_contains",
         lambda: (bloom.blocked_contains,
                  (jnp.zeros((m_p2,), jnp.uint8), bucket, idx2d)), {}),
        # -- hll ------------------------------------------------------------
        ("hll.bucket_rank", lambda: (hll.bucket_rank, (a64,)), {}),
        ("hll.insert_scatter",
         lambda: (hll.insert_scatter, (regs, bucket, rank)), {}),
        ("hll.insert_sorted",
         lambda: (hll.insert_sorted, (regs, bucket, rank)), {}),
        ("hll.add_hashes(scatter)",
         lambda: (pc(hll.add_hashes, impl="scatter"), (regs, a64)), {}),
        ("hll.add_hashes(sorted)",
         lambda: (pc(hll.add_hashes, impl="sorted"), (regs, a64)), {}),
        ("hll.merge", lambda: (hll.merge, (regs, regs)), {}),
        ("hll.merge_many",
         lambda: (hll.merge_many, (jnp.zeros((4, hll.M), jnp.int32),)), {}),
        ("hll.count", lambda: (hll.count, (regs,)), {}),
        # -- hashing --------------------------------------------------------
        ("hashing.murmur3_x64_128",
         lambda: (hashing.murmur3_x64_128, (data, lengths)), {}),
        ("hashing.murmur3_x64_128_u64",
         lambda: (hashing.murmur3_x64_128_u64, (a64,)), {}),
        ("hashing.murmur3_x64_128_u32",
         lambda: (hashing.murmur3_x64_128_u32, (a64.lo,)), {}),
        ("hashing.murmur2_64a",
         lambda: (hashing.murmur2_64a, (data, lengths)), {}),
        ("hashing.murmur2_64a_u64",
         lambda: (hashing.murmur2_64a_u64, (a64,)), {}),
        ("hashing.xxhash64", lambda: (hashing.xxhash64, (data, lengths)), {}),
        ("hashing.fmix64", lambda: (hashing.fmix64, (a64,)), {}),
        # -- u64 ------------------------------------------------------------
        ("u64.add", lambda: (u.add, (a64, b64)), {}),
        ("u64.mul", lambda: (u.mul, (a64, b64)), {}),
        ("u64.mul32", lambda: (u.mul32, (a64.lo, b64.lo)), {}),
        ("u64.xor", lambda: (u.xor, (a64, b64)), {}),
        ("u64.and_", lambda: (u.and_, (a64, b64)), {}),
        ("u64.or_", lambda: (u.or_, (a64, b64)), {}),
        ("u64.shl(7)", lambda: (pc(u.shl, n=7), (a64,)), {}),
        ("u64.shl(33)", lambda: (pc(u.shl, n=33), (a64,)), {}),
        ("u64.shr(7)", lambda: (pc(u.shr, n=7), (a64,)), {}),
        ("u64.shr(33)", lambda: (pc(u.shr, n=33), (a64,)), {}),
        ("u64.rotl(13)", lambda: (pc(u.rotl, n=13), (a64,)), {}),
        ("u64.eq", lambda: (u.eq, (a64, b64)), {}),
        ("u64.lt", lambda: (u.lt, (a64, b64)), {}),
        ("u64.where", lambda: (u.where, (pred, a64, b64)), {}),
        ("u64.ctz32", lambda: (u.ctz32, (a64.lo,)), {}),
        ("u64.clz32", lambda: (u.clz32, (a64.lo,)), {}),
        ("u64.ctz", lambda: (u.ctz, (a64,)), {}),
        ("u64.clz", lambda: (u.clz, (a64,)), {}),
        ("u64.popcount", lambda: (u.popcount, (a64,)), {}),
        ("u64.from_u32", lambda: (u.from_u32, (a64.lo,)), {}),
        ("u64.from_parts", lambda: (u.from_parts, (a64.hi, a64.lo)), {}),
        # -- pallas kernels (interpret-mode trace off-TPU) -------------------
        ("pallas.merge_stack",
         lambda: (pc(pk.merge_stack, block=64), (bank,)), {}),
        ("pallas.popcount_partials",
         lambda: (pc(pk.popcount_partials, block=1024), (small,)), {}),
        ("pallas.popcount_cells",
         lambda: (pc(pk.popcount_cells, block=1024), (small,)), {}),
        ("pallas.bitop_cells",
         lambda: (pc(pk.bitop_cells, op="or", block=1024), (stack,)), {}),
        ("pallas.delta_merge",
         lambda: (pc(pk.delta_merge, block=1024, interpret=True),
                  (stack, stack)), {}),
        # -- ingest kernels --------------------------------------------------
        ("ingest.hll_insert_segmented",
         lambda: (lambda r, b, k: ik.hll_insert_segmented(
             r, b, k, interpret=True),
             (regs, bucket, rank)), {}),
        ("ingest.bits_insert_segmented",
         lambda: (lambda c, i: ik.bits_insert_segmented(
             c, i, interpret=True),
             (small, jnp.zeros((16,), jnp.int32))), {}),
        ("ingest.hll_insert_segmented_lax",
         lambda: (ik.hll_insert_segmented_lax, (regs, bucket, rank)), {}),
        ("ingest.bits_insert_segmented_lax",
         lambda: (ik.bits_insert_segmented_lax, (small, idx1d)), {}),
        # -- mesh collectives (cluster data_plane="mesh"; traced over a
        # 1-device mesh — the shard_map body is device-count-invariant) --
        ("engine.hll_bank_merge_rows_collective",
         lambda: (pc(eng.hll_bank_merge_rows_collective,
                     mesh=get_mesh(1, SLOT_AXIS)),
                  (jnp.zeros((8, hll.M), jnp.int32),
                   jnp.zeros((4,), jnp.int32), jnp.int32(0))), {}),
        ("engine.hll_bank_merge_count_rows_collective",
         lambda: (pc(eng.hll_bank_merge_count_rows_collective,
                     mesh=get_mesh(1, SLOT_AXIS)),
                  (jnp.zeros((8, hll.M), jnp.int32),
                   jnp.zeros((4,), jnp.int32), jnp.int32(0))), {}),
        ("engine.hll_bank_count_rows_collective",
         lambda: (pc(eng.hll_bank_count_rows_collective,
                     mesh=get_mesh(1, SLOT_AXIS)),
                  (jnp.zeros((8, hll.M), jnp.int32),
                   jnp.zeros((4,), jnp.int32))), {}),
        ("engine.hll_bank_occupancy_collective",
         lambda: (pc(eng.hll_bank_occupancy_collective,
                     mesh=get_mesh(1, SLOT_AXIS)),
                  (jnp.zeros((8, hll.M), jnp.int32),)), {}),
        ("engine.hll_bank_add_bytes_rows[mesh]",
         lambda: (pc(eng.hll_bank_add_bytes_rows,
                     mesh=get_mesh(1, SLOT_AXIS)),
                  (jnp.zeros((8, hll.M), jnp.int32),
                   jnp.zeros((16, 8), jnp.uint8),
                   jnp.full((16,), 8, jnp.int32),
                   jnp.zeros((16,), jnp.int32), jnp.zeros((4,), jnp.int32),
                   jnp.ones((16,), bool))), {}),
        ("engine.hll_bank_grow_mesh",
         lambda: (pc(eng.hll_bank_grow_mesh, new_cap=16,
                     mesh=get_mesh(1, SLOT_AXIS)),
                  (jnp.zeros((8, hll.M), jnp.int32),)), {}),
    ]
    del jax
    return reg


def run_audits() -> list[Finding]:
    import jax

    findings: list[Finding] = []
    for name, thunk, allow_narrow in build_registry():
        try:
            fn, args = thunk()
            closed = jax.make_jaxpr(fn)(*args)
        except Exception as exc:  # noqa: BLE001 — any trace failure is a finding
            findings.append(Finding(
                "J000", f"<jaxpr:{name}>", 0,
                f"`{name}` failed to trace: {type(exc).__name__}: {exc}",
                "fix the op or its registry entry in "
                "tools/graftlint/jaxpr_audit.py",
            ))
            continue
        findings.extend(_check_one(name, closed, allow_narrow))
    return findings
