"""Delta ingest (PR 7 tentpole): host-folded register/bit deltas with one
fused multi-target merge per pipeline window.

Pins the acceptance contract: for hll_add/bloom_add/bitset_set the delta
path is bit-identical to the serial scatter path (device state AND per-op
results — PFADD "changed", bloom try_add "newly", bitset old bits), mixed
hll+bloom+bitset windows retire in ONE fused merge launch, the sparse
(idx, val) encoding kicks in exactly when it is smaller than the dense
plane, link bytes/key collapse below 1/8 of raw at large batches, the
planner's measured row overrides a stale dominated prior (the `sort`
regression), and delta merges bump read-cache epochs exactly like scatter.
"""

import numpy as np
import pytest

from redisson_tpu import native
from redisson_tpu.client import RedissonTPU
from redisson_tpu.config import Config, TpuConfig
from redisson_tpu.ingest import delta as delta_mod
from redisson_tpu.ingest.planner import IngestPlanner

needs_native = pytest.mark.skipif(
    not native.available(), reason="native fold library unavailable")


def _mk(ingest="delta"):
    return RedissonTPU.create(Config(tpu=TpuConfig(ingest=ingest)))


def _backend(c):
    return c._routing.sketch


def _bank_row(c, name):
    be = _backend(c)
    return np.asarray(be._ensure_bank())[be._rows[name]].copy()


# ---------------------------------------------------------------------------
# encoding: sparse-vs-dense crossover
# ---------------------------------------------------------------------------


def test_encode_picks_sparse_when_smaller():
    dense = np.zeros(1 << 14, np.uint8)
    dense[[3, 77, 9000]] = 5
    p = delta_mod.encode("hll_add", "t", dense, cells=1 << 14, packed=False,
                         nkeys=3, raw_bytes=24)
    assert p.sparse  # 3 * 5 B << 16384 B dense
    assert p.link_bytes < p.plane_bytes
    # Sparse entries are (idx, val) pairs padded to a pow2 with (0, 0);
    # real entries must round-trip.
    got = dict(zip(np.asarray(p.idx).tolist(), np.asarray(p.val).tolist()))
    assert got[3] == 5 and got[77] == 5 and got[9000] == 5


def test_encode_picks_dense_when_touched_fraction_large():
    dense = np.arange(1 << 14, dtype=np.uint8) % 50 + 1  # every cell touched
    p = delta_mod.encode("hll_add", "t", dense, cells=1 << 14, packed=False,
                         nkeys=1 << 14, raw_bytes=8 << 14)
    assert not p.sparse
    assert p.link_bytes == p.plane_bytes


# ---------------------------------------------------------------------------
# host folds vs numpy oracles
# ---------------------------------------------------------------------------


def test_fold_bitset_matches_numpy_packbits():
    rng = np.random.default_rng(3)
    idx = rng.integers(0, 4096, 700, np.int64)
    plane = delta_mod.fold_bitset([{"idx": idx}], 4096)
    want = np.zeros(4096, np.uint8)
    want[idx] = 1
    np.testing.assert_array_equal(plane, np.packbits(want))


@needs_native
def test_fold_hll_matches_device_registers():
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 2**63, 5000, np.uint64)
    cd = _mk("delta")
    cs = _mk("device")
    try:
        cd.get_hyper_log_log("d:fold").add_ints(keys)
        cs.get_hyper_log_log("d:fold").add_ints(keys)
        np.testing.assert_array_equal(
            _bank_row(cd, "d:fold"), _bank_row(cs, "d:fold"))
    finally:
        cd.shutdown()
        cs.shutdown()


# ---------------------------------------------------------------------------
# golden: delta vs serial op-by-op, per-op result parity
# ---------------------------------------------------------------------------


@needs_native
def test_hll_pfadd_changed_parity():
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 2**63, 3000, np.uint64)
    c = _mk("delta")
    try:
        h = c.get_hyper_log_log("d:pfadd")
        assert h.add_ints(keys) is True  # fresh registers: changed
        assert h.add_ints(keys) is False  # identical re-add: no register moved
        assert h.add_ints(rng.integers(0, 2**63, 64, np.uint64)) is True
    finally:
        c.shutdown()


@needs_native
def test_bloom_try_add_newly_parity_and_intra_batch_duplicates():
    rng = np.random.default_rng(6)
    a = rng.integers(0, 2**62, 500, np.uint64)
    cd, cs = _mk("delta"), _mk("device")
    try:
        for c in (cd, cs):
            f = c.get_bloom_filter("d:bloom")
            f.try_init(expected_insertions=50_000, false_probability=0.01)
        rd = cd.get_bloom_filter("d:bloom").add_ints(a)
        rs = cs.get_bloom_filter("d:bloom").add_ints(a)
        np.testing.assert_array_equal(np.asarray(rd), np.asarray(rs))
        # Re-add: every key already present on both paths.
        rd2 = cd.get_bloom_filter("d:bloom").add_ints(a)
        rs2 = cs.get_bloom_filter("d:bloom").add_ints(a)
        assert not np.asarray(rd2).any()
        np.testing.assert_array_equal(np.asarray(rd2), np.asarray(rs2))
        np.testing.assert_array_equal(
            np.asarray(_backend(cd).store.get("d:bloom").state),
            np.asarray(_backend(cs).store.get("d:bloom").state))
    finally:
        cd.shutdown()
        cs.shutdown()
    # Intra-batch duplicate: the fold is evolving (key i sees keys < i of
    # its own batch), matching serial one-key-at-a-time semantics.
    c = _mk("delta")
    try:
        f = c.get_bloom_filter("d:dup")
        f.try_init(expected_insertions=10_000, false_probability=0.01)
        dup = np.array([11, 22, 11], np.uint64)
        got = np.asarray(f.add_ints(dup))
        assert got[0] and got[1] and not got[2]
    finally:
        c.shutdown()


@needs_native
def test_bitset_old_bits_parity_across_windows():
    cd, cs = _mk("delta"), _mk("device")
    try:
        for c, out in ((cd, []), (cs, [])):
            b = c.get_bit_set("d:bits")
            out.append(np.asarray(b.set_bits([3, 9, 3000])))
            out.append(np.asarray(b.set_bits([3, 10])))  # 3 already set
            first, second = out
            np.testing.assert_array_equal(first, [False, False, False])
            np.testing.assert_array_equal(second, [True, False])
        np.testing.assert_array_equal(
            np.asarray(_backend(cd).store.get("d:bits").state),
            np.asarray(_backend(cs).store.get("d:bits").state))
    finally:
        cd.shutdown()
        cs.shutdown()


# ---------------------------------------------------------------------------
# mixed window: one fused merge launch for all three kinds
# ---------------------------------------------------------------------------


@needs_native
def test_mixed_window_single_fused_launch():
    rng = np.random.default_rng(7)
    c = _mk("delta")
    try:
        f = c.get_bloom_filter("d:mixb")
        f.try_init(expected_insertions=50_000, false_probability=0.01)
        be = _backend(c)
        runs0 = be.counters["delta_runs"]
        launches0 = be.counters["merge_launches"]
        # Submit all three kinds async in one burst: the executor's
        # delta-group steal stacks them into one window.
        futs = [
            c.get_hyper_log_log("d:mixh").add_ints_async(
                rng.integers(0, 2**63, 2000, np.uint64)),
            f.add_ints_async(rng.integers(0, 2**62, 1000, np.uint64)),
            c.get_bit_set("d:mixs").set_bits_async([1, 4, 900]),
        ]
        for fut in futs:
            fut.result(timeout=60)
        runs = be.counters["delta_runs"] - runs0
        launches = be.counters["merge_launches"] - launches0
        assert runs >= 1
        # Every window here fits one chunk: launches == windows, never
        # one launch per target/kind.
        assert launches == runs
        assert be.counters["delta_keys"] >= 3003
    finally:
        c.shutdown()


@needs_native
def test_link_bytes_collapse_below_eighth_of_raw():
    rng = np.random.default_rng(8)
    c = _mk("delta")
    try:
        c.get_hyper_log_log("d:link").add_ints(
            rng.integers(0, 2**63, 1 << 17, np.uint64))
        stats = _backend(c).ingest_stats()
        assert stats["raw_bytes"] == 8 << 17
        assert stats["link_bytes"] * 8 < stats["raw_bytes"]
        assert stats["delta_bytes_per_key"] < 1.0
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# satellite 1: planner priors never outlive the first-use measurement
# ---------------------------------------------------------------------------


def test_planner_measured_row_overrides_dominated_prior():
    def fake_measure(structure, n):
        return {"scatter": 1.0, "sort": 5.0, "segment": 2.0}

    p = IngestPlanner(platform="cpu", measure=fake_measure)
    # A stale prior claims `sort` is 10x cheaper than it really is — a
    # regression an early bench once hit.
    p.set_prior("hll", 1 << 16, {"sort": 0.1})
    plan = p.plan("hll", 1 << 16)
    assert plan.path == "scatter"  # measured winner, never the stale prior
    assert plan.costs["sort"] == 5.0  # measurement overrode the prior value


def test_planner_prior_only_fills_unmeasured_paths():
    def fake_measure(structure, n):
        return {"scatter": 3.0, "sort": 5.0, "segment": 4.0}

    p = IngestPlanner(platform="cpu", measure=fake_measure)
    # `delta` cannot be timed by the device loop; the prior supplies it.
    p.set_prior("hll", 1 << 16, {"delta": 0.5})
    plan = p.plan("hll", 1 << 16)
    assert plan.path == "delta"
    assert plan.costs["scatter"] == 3.0


def test_planner_auto_never_picks_dominated_path():
    def fake_measure(structure, n):
        return {"scatter": 1.0, "sort": 50.0, "segment": 2.0}

    p = IngestPlanner(platform="cpu", measure=fake_measure)
    for nkeys in (1 << 10, 1 << 14, 1 << 18, 1 << 21):
        plan = p.plan("hll", nkeys)
        assert plan.costs[plan.path] == min(plan.costs.values())
        assert plan.path != "sort"


# ---------------------------------------------------------------------------
# satellite 2: delta merges bump read-cache epochs exactly like scatter
# ---------------------------------------------------------------------------


@needs_native
class TestDeltaEpochInvalidation:
    def test_hll(self):
        c = _mk("delta")
        try:
            h = c.get_hyper_log_log("d:ep:h")
            h.add_ints(np.arange(1000, dtype=np.uint64))
            first = h.count()
            cache = _backend(c).read_cache
            hits0 = cache.hits
            assert h.count() == first
            assert cache.hits > hits0  # second read served from cache
            h.add_ints(np.arange(1000, 4000, dtype=np.uint64))
            assert h.count() > first  # delta merge bumped the epoch
        finally:
            c.shutdown()

    def test_bitset(self):
        c = _mk("delta")
        try:
            b = c.get_bit_set("d:ep:b")
            b.set_bits([1, 5, 9])
            assert b.cardinality() == 3
            cache = _backend(c).read_cache
            hits0 = cache.hits
            assert b.cardinality() == 3
            assert cache.hits > hits0
            b.set_bits([100, 200])
            assert b.cardinality() == 5  # not the stale cached 3
        finally:
            c.shutdown()

    def test_bloom(self):
        c = _mk("delta")
        try:
            f = c.get_bloom_filter("d:ep:f")
            f.try_init(expected_insertions=10_000, false_probability=0.01)
            f.add_ints(np.array([7, 8], np.uint64))
            assert f.count() >= 1
            cache = _backend(c).read_cache
            hits0 = cache.hits
            f.count()
            assert cache.hits > hits0
            f.add_ints(np.array([9, 10, 11], np.uint64))
            assert f.count() >= 3  # delta merge invalidated the count
        finally:
            c.shutdown()


# ---------------------------------------------------------------------------
# satellite 6: backend gauges reach the metrics registry
# ---------------------------------------------------------------------------


@needs_native
def test_delta_gauges_in_metrics_snapshot():
    c = _mk("delta")
    try:
        c.get_hyper_log_log("d:gauge").add_ints(
            np.arange(50_000, dtype=np.uint64) * 2654435761 % (2**61))
        snap = c.metrics.snapshot()["gauges"]
        assert snap["backend.link_bytes"] > 0
        assert snap["backend.raw_bytes"] == 50_000 * 8
        assert snap["backend.merge_launches"] >= 1
        assert snap["backend.delta_fold_s"] > 0.0
        assert snap["backend.delta_bytes_per_key"] > 0.0
    finally:
        c.shutdown()
