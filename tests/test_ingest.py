"""Streaming ingest subsystem (redisson_tpu/ingest/).

Covers the three pieces the subsystem owns — the Pallas segmented-scatter
insert kernel (vs its lax fallback AND the pure-python golden oracle),
the ingest rule that picks a batch's path, and the double-buffered staging
pipeline (results ordered, batch N+1 staged while batch N dispatches) —
plus the 64-bit BITCOUNT guard (>2^31 set bits on both tiers) and a
tier-1-safe smoke of ``bench.py --quick``.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from redisson_tpu.ingest import kernels
from redisson_tpu.ingest.pipeline import StagingPipeline
from tests import golden

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# segmented-scatter kernel
# ---------------------------------------------------------------------------


def _golden_bucket_rank(keys, p=14):
    """Per-key (bucket, rank) from the golden redis hash — independent of
    every repo kernel, so kernel-vs-oracle equality breaks the
    self-consistency cycle."""
    m = 1 << p
    idx, rank = [], []
    for key in keys:
        h = golden.murmur2_64a(key)
        idx.append(h & (m - 1))
        rest = (h >> p) | (1 << (64 - p))
        r = 1
        while rest & 1 == 0:
            r += 1
            rest >>= 1
        rank.append(r)
    return np.array(idx, np.int32), np.array(rank, np.int32)


def test_hll_segmented_matches_golden_oracle():
    keys = [b"key:%d" % i for i in range(3000)]
    expect = golden.redis_hll_registers(keys)
    bucket, rank = _golden_bucket_rank(keys)
    regs = np.zeros(1 << 14, np.int32)
    out_pallas = np.asarray(
        kernels.hll_insert_segmented(regs, bucket, rank, interpret=True))
    out_lax = np.asarray(kernels.hll_insert_segmented_lax(regs, bucket, rank))
    np.testing.assert_array_equal(out_pallas, expect.astype(np.int32))
    np.testing.assert_array_equal(out_lax, expect.astype(np.int32))


def test_hll_segmented_matches_lax_fallback():
    rng = np.random.default_rng(0)
    m = 1 << 14
    regs = rng.integers(0, 20, m, np.int32)
    for n in (1, 127, 4096, 20011):
        bucket = rng.integers(0, m, n, np.int32)
        rank = rng.integers(1, 51, n, np.int32)
        got = np.asarray(kernels.hll_insert_segmented(
            regs, bucket, rank, interpret=True))
        want = np.asarray(kernels.hll_insert_segmented_lax(regs, bucket, rank))
        np.testing.assert_array_equal(got, want)


def test_hll_segmented_empty_batch():
    regs = np.arange(1 << 14, dtype=np.int32) % 7
    empty = np.zeros((0,), np.int32)
    out = np.asarray(kernels.hll_insert_segmented(
        regs, empty, empty, interpret=True))
    np.testing.assert_array_equal(out, regs)


def test_bits_segmented_matches_lax_and_numpy():
    rng = np.random.default_rng(1)
    ncells = 70001  # deliberately not a tile multiple
    cells = (rng.random(ncells) < 0.01).astype(np.uint8)
    for n in (1, 500, 8192):
        idx = rng.integers(0, ncells, n, np.int32)
        want = cells.copy()
        want[idx] = 1
        got = np.asarray(kernels.bits_insert_segmented(
            cells, idx, interpret=True))
        lax_got = np.asarray(kernels.bits_insert_segmented_lax(cells, idx))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(lax_got, want)


def test_engine_segment_impl_matches_scatter():
    # The engine-level wiring: forcing impl="segment" through the public
    # batch entrypoints must land the same registers as the scatter path.
    from redisson_tpu import engine

    rng = np.random.default_rng(2)
    keys = rng.integers(0, 2**63, 5000, np.uint64)
    packed = keys.view(np.uint32).reshape(-1, 2)
    import jax.numpy as jnp

    out = {}
    for impl in ("scatter", "segment"):
        # fresh bank per impl: the batch entrypoint donates its input
        bank = jnp.zeros((4, 16384), jnp.int32)
        new, changed = engine.hll_bank_add_packed(
            bank, packed, np.int32(keys.size), np.int32(1), 0, "murmur3",
            impl=impl)
        out[impl] = np.asarray(new)
        assert bool(np.asarray(changed)[1])
    np.testing.assert_array_equal(out["scatter"], out["segment"])


def test_client_forced_segment_estimate_matches_scatter():
    # The config knob end to end: ingest="segment" and "scatter" are the
    # same sketch, so the estimates must be identical (not just close).
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    keys = np.random.default_rng(3).integers(0, 2**63, 40000, np.uint64)
    counts = {}
    for path in ("scatter", "segment"):
        cfg = Config()
        cfg.use_tpu().ingest = path
        c = RedissonTPU.create(cfg)
        try:
            h = c.get_hyper_log_log("ingest:%s" % path)
            h.add_ints(keys)
            counts[path] = h.count()
        finally:
            c.shutdown()
    assert counts["scatter"] == counts["segment"]
    assert abs(counts["scatter"] - keys.size) / keys.size < 0.05


# ---------------------------------------------------------------------------
# ingest rule
# ---------------------------------------------------------------------------


def _link(transfer_ns_per_byte, fold_ns_per_key=4.0):
    """A measured LinkProfile without the probe."""
    from redisson_tpu.backend_tpu import LinkProfile

    prof = object.__new__(LinkProfile)
    prof.transfer_ns_per_byte = transfer_ns_per_byte
    prof.fold_ns_per_key = fold_ns_per_key
    return prof


_SLOW, _FAST = 10.0, 0.1  # ns/byte: 8 B/key costs 80 / 0.8 ns vs a 4 ns fold
_BIG = 1 << 20


@pytest.mark.parametrize("link,native_ok,nkeys,kw,want", [
    (_SLOW, False, _BIG, {}, "sort"),                       # native missing
    (_SLOW, True, _BIG, {"family": "redis"}, "sort"),       # redis hash
    (_SLOW, True, (1 << 16) - 1, {}, "sort"),               # below the floor
    (_SLOW, True, _BIG, {}, "hostfold"),                    # classic run
    (_SLOW, True, _BIG, {"group": True}, "delta"),          # target group
    (_FAST, True, _BIG, {"group": True}, "sort"),           # fast link
    (_SLOW, True, _BIG, {"bits": True, "group": True,       # plane cheaper
                         "plane_bytes": 1 << 17}, "delta"),
    (_SLOW, True, 1 << 16, {"bits": True, "group": True,    # raw keys cheaper
                            "plane_bytes": 1 << 25, "raw_per_key": 4},
     "scatter"),
], ids=["native_missing", "redis_hash", "below_min_keys",
        "slow_link_classic_hostfold", "slow_link_group_delta",
        "fast_link_device", "bits_plane_cheaper", "bits_raw_cheaper"])
def test_ingest_rule(monkeypatch, link, native_ok, nkeys, kw, want):
    from redisson_tpu import backend_tpu, native

    monkeypatch.setattr(native, "available", lambda: native_ok)
    monkeypatch.setattr(backend_tpu, "link_profile", lambda dev: _link(link))
    assert backend_tpu.ingest_rule("auto", nkeys, None, hll_impl="sort",
                                   **kw) == want


def test_rule_inputs_table_never_probes(monkeypatch):
    from redisson_tpu import backend_tpu

    table = backend_tpu.RuleInputs("dev0")
    assert table.table() == {}
    assert "dev0" not in backend_tpu._LINK_PROFILES  # no probe taken
    monkeypatch.setitem(backend_tpu._LINK_PROFILES, "dev0",
                        _link(0.5, float("inf")))
    got = json.loads(json.dumps(table.table(), allow_nan=False))
    assert got == {"transfer_ns_per_byte": 0.5, "fold_ns_per_key": None,
                   "fold_min_keys": backend_tpu.HOSTFOLD_MIN_KEYS}


# ---------------------------------------------------------------------------
# staging pipeline
# ---------------------------------------------------------------------------


def test_pipeline_results_ordered():
    pipe = StagingPipeline(depth=2)
    out = pipe.run(list(range(7)),
                   stage=lambda c: c * 10,
                   dispatch=lambda i, staged: staged + i)
    assert out == [c * 10 + i for i, c in enumerate(range(7))]


def test_pipeline_overlaps_stage_with_dispatch():
    # The double-buffer contract: chunk N+1 must be STAGED (host prep +
    # transfer) before chunk N's dispatch completes.
    trace = []
    pipe = StagingPipeline(depth=2, trace=trace)

    def stage(c):
        time.sleep(0.01)
        return c

    def dispatch(i, staged):
        time.sleep(0.05)
        return staged

    pipe.run([0, 1, 2], stage, dispatch)
    t = {(ev, i): ts for ev, i, ts in trace}
    assert t[("stage_start", 1)] < t[("dispatch_end", 0)]
    assert t[("stage_end", 1)] < t[("dispatch_end", 0)] + 0.05


def test_pipeline_dispatch_serial_and_on_caller_thread():
    caller = threading.get_ident()
    seen = []
    pipe = StagingPipeline(depth=2)

    def dispatch(i, staged):
        assert threading.get_ident() == caller
        seen.append(i)
        return staged

    pipe.run([5, 6, 7], stage=lambda c: c, dispatch=dispatch)
    assert seen == [0, 1, 2]


def test_pipeline_propagates_stage_error():
    pipe = StagingPipeline(depth=2)

    def stage(c):
        if c == 2:
            raise ValueError("boom in stage")
        return c

    with pytest.raises(ValueError, match="boom in stage"):
        pipe.run([0, 1, 2, 3], stage, lambda i, s: s)


def test_pipeline_propagates_dispatch_error():
    pipe = StagingPipeline(depth=2)

    def dispatch(i, staged):
        if i == 1:
            raise RuntimeError("boom in dispatch")
        return staged

    with pytest.raises(RuntimeError, match="boom in dispatch"):
        pipe.run([0, 1, 2, 3], lambda c: c, dispatch)


def test_pipeline_empty_input():
    assert StagingPipeline().run([], lambda c: c, lambda i, s: s) == []


# ---------------------------------------------------------------------------
# 64-bit BITCOUNT (satellite: popcount past 2^31 set bits)
# ---------------------------------------------------------------------------


def test_bitset_combine_partials_past_int31():
    from redisson_tpu.ops import bitset

    # 4096 chunks of 2^20 set bits each = 2^32 total: overflows int32 (and
    # even its absolute value) but each PARTIAL is chunk-bounded. The
    # combine must run in 64 bits host-side.
    partials = np.full((4096, 1), 1 << 20, np.int32)
    assert bitset.combine_partials(partials) == 1 << 32


def test_sharded_combine_partials_past_int31():
    from redisson_tpu.parallel import sharded_bits

    partials = np.full((5000,), 1 << 20, np.int32)
    assert sharded_bits.combine_partials(partials) == 5000 * (1 << 20)


def test_bitset_cardinality_chunked_partials_agree():
    from redisson_tpu.ops import bitset

    rng = np.random.default_rng(4)
    cells = (rng.random(3_000_000) < 0.37).astype(np.uint8)
    expect = int(cells.sum(dtype=np.int64))
    assert bitset.cardinality(cells) == expect
    parts = np.asarray(bitset.cardinality_partials(cells))
    assert parts.dtype == np.int32
    assert bitset.combine_partials(parts) == expect


def test_pallas_popcount_partials_combine():
    from redisson_tpu.ops import bitset, pallas_kernels

    rng = np.random.default_rng(5)
    cells = (rng.random(600_000) < 0.5).astype(np.uint8)
    parts = np.asarray(pallas_kernels.popcount_partials(cells))
    assert bitset.combine_partials(parts) == int(cells.sum(dtype=np.int64))


@pytest.mark.slow
def test_bitset_cardinality_real_past_int31():
    # Real >2^31 allocation (2.2 GB of cells) — slow tier only.
    from redisson_tpu.ops import bitset

    n = (1 << 31) + (1 << 20)
    cells = np.ones(n, np.uint8)
    assert bitset.cardinality(cells) == n


# ---------------------------------------------------------------------------
# bench smoke (tier-1 safe: CPU, tiny batches)
# ---------------------------------------------------------------------------


def test_bench_quick_smoke():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    # The roofline must price the segmented kernel too, and the recorded
    # ingest decision is the link rule's: a 2^14-key batch stays below
    # the fold's floor, so it inserts on the device.
    assert result["kernel_segment_inserts_per_sec"] > 0
    assert "pct_of_roofline" in result
    assert "pct_of_roofline_segment" in result
    assert result["ingest"]["path"] == "scatter"
    assert result["ingest"]["fold_min_keys"] == 1 << 16
