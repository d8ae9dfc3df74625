"""Window megakernel (PR 12 tentpole): one fused launch per pipeline
window, driven by an on-device command tape.

Layers:

1. Kernel — window_merge_pallas (interpret mode) vs window_merge_lax vs
   a numpy oracle: bit-identical merged rows + changed flags on
   randomized mixed dense/packed tapes.
2. Encode — ingest/tape.py tape layout: HLL-first ordering, pow2
   padding with identity rows, sparse-plane re-densification round-trip,
   unknown-kind rejection.
3. Property — randomized mixed hll/bloom/bitset windows through the
   real client with ingest="tape" vs the serial scatter oracle:
   per-op results (PFADD changed, bloom newly incl. intra-window
   duplicates, bitset old-bit reads) and the engine digest must be
   bit-identical — including under kernel_launch fault injection with
   serve retries absorbing the injected tape fault.
4. Satellites — exactly one launch per tape window; the chunked
   fallback when a window overflows the arena budget stays correct;
   per-chunk failure isolation in the delta path (a failed chunk
   commits nothing, other chunks commit and bump epochs); donation in
   the merge kernels + the memstat-ledger no-spike contract.
"""

import time

import numpy as np
import pytest

from redisson_tpu import native
from redisson_tpu.client import RedissonTPU
from redisson_tpu.config import Config, TpuConfig
from redisson_tpu.fault import inject
from redisson_tpu.fault.taxonomy import RetryableFault
from redisson_tpu.ingest import delta as delta_mod
from redisson_tpu.ingest import tape as tape_mod
from redisson_tpu.ops import window_kernel as wk

from tests.test_persist import engine_digest

needs_native = pytest.mark.skipif(
    not native.available(), reason="native fold library unavailable")


@pytest.fixture(autouse=True)
def _clean_fault_globals():
    inject.uninstall()
    yield
    inject.uninstall()


def _mk(ingest, plan=None):
    cfg = Config(tpu=TpuConfig(ingest=ingest))
    if plan is not None:
        sc = cfg.use_serve()
        sc.retry_interval_ms = 5
        fc = cfg.use_faults()
        fc.plan = plan
    return RedissonTPU.create(cfg)


def _backend(c):
    return c._routing.sketch


# ---------------------------------------------------------------------------
# 1. kernel: pallas-interpret vs lax vs numpy oracle
# ---------------------------------------------------------------------------


def _random_tape(rng, t2, lanes):
    """A randomized raw tape: mixed op codes, random lengths, random
    old/wire rows, plus the numpy-oracle expected outputs."""
    table = np.zeros((t2, wk.TABLE_COLS), np.int32)
    old = np.zeros((t2, lanes), np.uint8)
    wire = np.zeros((t2, lanes), np.uint8)
    want = np.zeros((t2, lanes), np.uint8)
    want_changed = np.zeros((t2,), bool)
    for t in range(t2):
        op = rng.choice([wk.OP_PAD, wk.OP_HLL, wk.OP_BLOOM, wk.OP_BITSET])
        if op == wk.OP_PAD:
            length = 0
        else:
            length = int(rng.integers(1, lanes + 1))
        table[t] = (op, -1, 0, length)
        if op == wk.OP_HLL:
            old[t] = rng.integers(0, 65, lanes, np.uint8)
            wire[t, :length] = rng.integers(0, 65, length, np.uint8)
            delta = wire[t].copy()
            delta[length:] = 0
        else:
            old[t] = rng.integers(0, 2, lanes, np.uint8)
            cells = rng.integers(0, 2, lanes, np.uint8)
            cells[length:] = 0
            wire[t, : lanes // 8] = np.packbits(cells)
            delta = cells
        want[t] = np.maximum(old[t], delta)
        want_changed[t] = bool((want[t] != old[t]).any())
    return table, old, wire, want, want_changed


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_window_kernel_interpret_lax_oracle_identical(seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    t2, lanes = 4, 256
    table, old, wire, want, want_changed = _random_tape(rng, t2, lanes)
    m_lax, c_lax = wk.window_merge_lax(
        jnp.asarray(old), jnp.asarray(wire), jnp.asarray(table))
    np.testing.assert_array_equal(np.asarray(m_lax), want)
    np.testing.assert_array_equal(np.asarray(c_lax), want_changed)
    m_pl, c_pl = wk.window_merge_pallas(
        jnp.asarray(old), jnp.asarray(wire), jnp.asarray(table),
        block=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(m_pl), want)
    np.testing.assert_array_equal(np.asarray(c_pl), want_changed)


def test_window_kernel_pad_rows_are_identity():
    import jax.numpy as jnp

    old = np.full((2, 64), 7, np.uint8)
    wire = np.full((2, 64), 255, np.uint8)  # garbage: length 0 masks it
    table = np.array([[wk.OP_PAD, -1, 0, 0]] * 2, np.int32)
    merged, changed = wk.window_merge_lax(
        jnp.asarray(old), jnp.asarray(wire), jnp.asarray(table))
    np.testing.assert_array_equal(np.asarray(merged), old)
    assert not np.asarray(changed).any()


# ---------------------------------------------------------------------------
# 2. encode_window
# ---------------------------------------------------------------------------


def _plane(kind, target, dense, cells, packed):
    return delta_mod.encode(kind, target, dense, cells=cells, packed=packed,
                            nkeys=1, raw_bytes=8)


def test_encode_window_orders_hll_first_and_pads_pow2():
    bits = np.zeros(128, np.uint8)
    bits[0] = 255
    planes = [
        _plane("bitset_set", "b", bits, 1024, True),
        _plane("hll_add", "h", np.full(16384, 3, np.uint8), 16384, False),
        _plane("bloom_add", "f", bits, 1024, True),
    ]
    tp = tape_mod.encode_window(planes, lambda name: 5)
    assert [p.kind for p in tp.planes] == [
        "hll_add", "bitset_set", "bloom_add"]
    assert tp.table.shape == (4, wk.TABLE_COLS)  # 3 entries pad to pow2
    assert tp.n_hll == 1 and tp.hll_rows.tolist() == [5]
    assert tp.table[0].tolist()[:2] == [wk.OP_HLL, 5]
    assert tp.table[3, 0] == wk.OP_PAD and tp.table[3, 3] == 0
    assert tp.lanes == 16384
    # Wire width: pow2 of the max plane_bytes (the 16 KB HLL plane).
    assert tp.wire.shape == (4, 16384)
    assert tp.link_bytes == tp.table.nbytes + tp.wire.nbytes


def test_encode_window_redensifies_sparse_planes():
    dense = np.zeros(16384, np.uint8)
    dense[[7, 99, 5000]] = 9
    p = _plane("hll_add", "h", dense, 16384, False)
    assert p.sparse  # 3 entries << dense plane
    tp = tape_mod.encode_window([p], lambda name: 0)
    np.testing.assert_array_equal(tp.wire[0, :16384], dense)


def test_encode_window_rejects_unknown_kind():
    p = _plane("hll_add", "h", np.zeros(16384, np.uint8), 16384, False)
    object.__setattr__(p, "kind", "zadd")
    with pytest.raises(ValueError, match="no op code"):
        tape_mod.encode_window([p], lambda name: 0)


# ---------------------------------------------------------------------------
# 3. property: tape vs serial scatter oracle, with and without faults
# ---------------------------------------------------------------------------


def _play_workload(c, rng, sync, disjoint=False):
    """One randomized mixed workload; returns every per-op result. Same
    rng seed -> identical op stream, so tape and oracle see the same
    submissions in the same order. `sync` submits op-by-op (the serial
    oracle); async submits each round as one burst (one tape window of
    mixed kinds, including TWO bloom ops on one target in one window —
    the intra-window duplicate case). `disjoint` draws the two bloom
    batches from the full key space instead of a shared pool: serve
    retries replay failed ops individually and do not promise to keep
    two same-target ops in their original relative order, so a
    fault-injection run can only pin per-op results when no key's
    "newly" answer depends on which sibling op folded first."""
    results = []
    hs = [c.get_hyper_log_log(f"tp:h{i}") for i in range(2)]
    bf = c.get_bloom_filter("tp:bloom")
    bf.try_init(expected_insertions=50_000, false_probability=0.01)
    bs = c.get_bit_set("tp:bits")
    for _ in range(3):
        hll_keys = rng.integers(0, 2**61, 1500, np.uint64)
        pool = rng.integers(0, 2**61, 400, np.uint64)
        # Cross-op duplicates INSIDE one window: both bloom ops draw from
        # one small pool, so op b's "newly" must see op a's bits (the
        # in-order evolving fold). Batches stay duplicate-free internally:
        # the device-scatter oracle evaluates a batch against pre-op
        # state, so intra-BATCH duplicate semantics are pinned separately
        # (test_tape_intra_batch_bloom_duplicates_are_serial).
        if disjoint:
            bloom_a = np.unique(rng.integers(0, 2**61, 300, np.uint64))
            bloom_b = np.unique(rng.integers(0, 2**61, 300, np.uint64))
        else:
            bloom_a = np.unique(rng.choice(pool, 300))
            bloom_b = np.unique(rng.choice(pool, 300))
        bits_idx = rng.integers(0, 1 << 14, 200, np.int64)
        bits_idx[:20] = bits_idx[20:40]  # duplicate indices in one op
        if sync:
            results.append(bool(hs[0].add_ints(hll_keys)))
            results.append(bool(hs[1].add_ints(hll_keys[:700])))
            results.append(np.asarray(bf.add_ints(bloom_a)).tolist())
            results.append(np.asarray(bf.add_ints(bloom_b)).tolist())
            results.append(np.asarray(bs.set_bits(bits_idx)).tolist())
        else:
            futs = [
                hs[0].add_ints_async(hll_keys),
                hs[1].add_ints_async(hll_keys[:700]),
                bf.add_ints_async(bloom_a),
                bf.add_ints_async(bloom_b),
                bs.set_bits_async(bits_idx),
            ]
            out = [f.result(timeout=120) for f in futs]
            results.append(bool(out[0]))
            results.append(bool(out[1]))
            results.extend(np.asarray(o).tolist() for o in out[2:])
    return results


def _digest(c):
    _backend(c)._bloom_device_sync("tp:bloom")  # host-mirror path parity
    return engine_digest(c)


@needs_native
@pytest.mark.parametrize("seed", [11, 12])
def test_tape_window_matches_serial_scatter_oracle(seed):
    ct, cs = _mk("tape"), _mk("scatter")
    try:
        res_t = _play_workload(ct, np.random.default_rng(seed), sync=False)
        res_s = _play_workload(cs, np.random.default_rng(seed), sync=True)
        assert res_t == res_s
        assert _digest(ct) == _digest(cs)
        stats = _backend(ct).ingest_stats()
        assert stats["tape_runs"] >= 1
        assert stats["delta_runs"] == 0  # every window fit the tape arena
        assert stats["launches_per_window"] == 1.0
    finally:
        ct.shutdown()
        cs.shutdown()


@needs_native
def test_tape_intra_batch_bloom_duplicates_are_serial():
    """Duplicates INSIDE one bloom op fold serially (key i sees keys < i
    of its own batch), matching one-key-at-a-time semantics — same
    contract the delta path pins."""
    c = _mk("tape")
    try:
        f = c.get_bloom_filter("dup:bloom")
        f.try_init(expected_insertions=10_000, false_probability=0.01)
        got = np.asarray(f.add_ints(np.array([11, 22, 11], np.uint64)))
        assert got[0] and got[1] and not got[2]
    finally:
        c.shutdown()


@needs_native
def test_tape_window_fault_injection_retries_to_oracle_state():
    """An injected kernel_launch fault at the tape seam fires BEFORE the
    window commits anything, so serve retries replay the ops and the
    final state + per-op results stay bit-identical to the fault-free
    serial oracle."""
    plan = [{"seam": "kernel_launch", "kind": "tape", "nth": 1},
            {"seam": "kernel_launch", "kind": "tape", "nth": 3}]
    ct, cs = _mk("tape", plan=plan), _mk("scatter")
    try:
        rt, rs = np.random.default_rng(31), np.random.default_rng(31)
        res_t = _play_workload(ct, rt, sync=False, disjoint=True)
        res_s = _play_workload(cs, rs, sync=True, disjoint=True)
        assert res_t == res_s
        assert _digest(ct) == _digest(cs)
        inj = inject.installed()
        assert inj is not None and inj.injected >= 1
    finally:
        ct.shutdown()
        cs.shutdown()


@needs_native
def test_tape_fault_without_retry_fails_window_whole():
    """No serve tier: the injected tape fault surfaces on EVERY op of the
    window (whole-window failure unit) and nothing commits — the bank
    row stays empty and the store objects keep their pre-window state."""
    c = _mk("tape")
    try:
        inject.install(inject.FaultInjector(inject.FaultPlan(rules=[
            inject.FaultRule(seam="kernel_launch", kind="tape", nth=1)])))
        be = _backend(c)
        futs = [
            c.get_hyper_log_log("tf:h").add_ints_async(
                np.arange(2000, dtype=np.uint64)),
            c.get_bit_set("tf:b").set_bits_async([1, 2, 3]),
        ]
        for f in futs:
            with pytest.raises(RetryableFault):
                f.result(timeout=60)
        assert be._epochs.get("tf:h", 0) == 0
        assert be._epochs.get("tf:b", 0) == 0
        # Retry after the fault: clean state, normal tape retire.
        inject.uninstall()
        assert c.get_hyper_log_log("tf:h").add_ints(
            np.arange(2000, dtype=np.uint64)) is True
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# 4a. one fused launch per window / overflow fallback
# ---------------------------------------------------------------------------


@needs_native
def test_tape_retires_mixed_window_in_one_launch():
    rng = np.random.default_rng(7)
    c = _mk("tape")
    try:
        be = _backend(c)
        f = c.get_bloom_filter("t1:bloom")
        f.try_init(expected_insertions=50_000, false_probability=0.01)
        futs = [
            c.get_hyper_log_log("t1:h").add_ints_async(
                rng.integers(0, 2**63, 2000, np.uint64)),
            f.add_ints_async(rng.integers(0, 2**62, 1000, np.uint64)),
            c.get_bit_set("t1:bits").set_bits_async([1, 4, 900]),
        ]
        for fu in futs:
            fu.result(timeout=60)
        stats = be.ingest_stats()
        assert stats["tape_runs"] >= 1
        assert stats["window_launches"] == stats["tape_runs"]
        assert stats["launches_per_window"] == 1.0
        assert stats["launch_us"] > 0.0
    finally:
        c.shutdown()


@needs_native
def test_tape_overflow_falls_back_to_chunked_and_stays_correct():
    """A window too large for one tape arena retires through the chunked
    delta path — including the deferred bitset pre-merge packs the tape
    folds skipped — and stays bit-identical to the oracle."""
    c, cs = _mk("tape"), _mk("scatter")
    try:
        be = _backend(c)
        # Budget below one 16K-lane HLL plane: any window containing an
        # HLL plane overflows the tape arena and falls back.
        be.DELTA_STACK_CELLS = 1 << 13
        for cl in (c, cs):
            b = cl.get_bit_set("ov:bits")
            first = np.asarray(b.set_bits([3, 9, 3000]))
            np.testing.assert_array_equal(first, [False, False, False])
        # Mixed hll+bitset burst -> ONE window that overflows: the
        # fallback must issue the deferred bitset pre-merge pack, so the
        # old-bit reads still see pre-window state.
        fh = c.get_hyper_log_log("ov:h").add_ints_async(
            np.arange(3000, dtype=np.uint64))
        fb = c.get_bit_set("ov:bits").set_bits_async([3, 10, 5000])
        hot = bool(fh.result(timeout=60))
        old_bits = np.asarray(fb.result(timeout=60))
        hos = bool(cs.get_hyper_log_log("ov:h").add_ints(
            np.arange(3000, dtype=np.uint64)))
        old_s = np.asarray(cs.get_bit_set("ov:bits").set_bits([3, 10, 5000]))
        assert hot == hos
        np.testing.assert_array_equal(old_bits, old_s)
        np.testing.assert_array_equal(old_bits, [True, False, False])
        assert be.counters["delta_runs"] >= 1  # the fallback engaged
        assert be.counters["tape_runs"] >= 1  # the small window taped
        np.testing.assert_array_equal(
            np.asarray(be.store.get("ov:bits").state),
            np.asarray(_backend(cs).store.get("ov:bits").state))
    finally:
        c.shutdown()
        cs.shutdown()


# ---------------------------------------------------------------------------
# 4b. per-chunk failure isolation in the chunked delta path
# ---------------------------------------------------------------------------


@needs_native
def test_delta_chunk_failure_isolated_to_its_own_targets():
    """Two HLL targets forced into two merge chunks; an injected
    kernel_launch fault on the second chunk must leave the first chunk
    COMMITTED (registers live, epoch bumped) and fail only the second
    chunk's ops, with its bank row untouched and epoch unbumped."""
    c = _mk("delta")
    try:
        be = _backend(c)
        # One 16384-lane HLL plane fills the whole budget -> one plane
        # per chunk, two chunks per window.
        be.DELTA_STACK_CELLS = 1 << 14
        inject.install(inject.FaultInjector(inject.FaultPlan(rules=[
            inject.FaultRule(seam="kernel_launch", kind="delta_merge",
                             nth=2)])))
        ha = c.get_hyper_log_log("iso:a")
        hb = c.get_hyper_log_log("iso:b")
        fa = ha.add_ints_async(np.arange(2000, dtype=np.uint64))
        fb = hb.add_ints_async(np.arange(5000, 7000, dtype=np.uint64))
        outcomes = {}
        for name, fut in (("iso:a", fa), ("iso:b", fb)):
            try:
                outcomes[name] = bool(fut.result(timeout=60))
            except RetryableFault:
                outcomes[name] = "failed"
        committed = [n for n, v in outcomes.items() if v is True]
        failed = [n for n, v in outcomes.items() if v == "failed"]
        assert len(committed) == 1 and len(failed) == 1, outcomes
        bank = np.asarray(be._ensure_bank())
        assert bank[be._rows[committed[0]]].any()
        assert not bank[be._rows[failed[0]]].any()
        assert be._epochs.get(committed[0], 0) >= 1
        assert be._epochs.get(failed[0], 0) == 0
        # Exactly one chunk merged before the fault killed the other.
        assert be.counters["merge_launches"] == 1
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# 4c. donation + memstat-ledger no-spike
# ---------------------------------------------------------------------------


def test_merge_kernels_declare_donation():
    """delta_merge_stack / merge_stack / tape_apply donate their old
    stacks so the merge lands in place — peak HBM never holds two copies
    of the old state. Donation shows up either as an input->output alias
    in the lowering (where the backend honors it) or as the
    donated-buffers-unusable warning (CPU) — its absence in BOTH means
    the donate_argnums declaration was dropped."""
    import warnings

    import jax.numpy as jnp

    from redisson_tpu import engine
    from redisson_tpu.ops import pallas_kernels as pk

    def donates(lower_fn):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            txt = lower_fn().as_text()
        warned = any("donated buffers were not usable" in str(x.message)
                     for x in w)
        return warned or "tf.aliasing_output" in txt

    assert donates(lambda: engine.delta_merge_stack.lower(
        jnp.zeros((2, 2048), jnp.uint8), jnp.zeros((2, 2048), jnp.uint8)))
    assert donates(lambda: pk.merge_stack.lower(
        jnp.zeros((8, 1024), jnp.int32)))
    assert donates(lambda: engine.tape_apply.lower(
        jnp.zeros((4, 16384), jnp.int32),          # bank
        jnp.zeros((2, 16384), jnp.uint8),          # wire
        jnp.zeros((2, 4), jnp.int32),              # table
        jnp.zeros((1,), jnp.int32),                # hll_rows
        (),                                        # store_old
        n_hll=1, lanes=16384, want_old=False))


@needs_native
@pytest.mark.parametrize("ingest", ["delta", "tape"])
def test_merge_ledger_no_spike_and_scratch_drains(ingest):
    """Repeated same-shape merges must not move the ledger at all: the
    donated in-place merge swaps same-size arrays (on_resize is a no-op),
    so live_bytes stays flat, the peak high-water never exceeds the
    steady live total, verify() reports zero drift, and the in-flight
    delta scratch meter drains back to zero."""
    c = _mk(ingest)
    try:
        be = _backend(c)
        b = c.get_bit_set("ms:bits")
        b.set_bits(np.arange(0, 4096, 2, dtype=np.int64))
        live0 = c.memstat.live_bytes()
        peak0 = c.memstat.peak_bytes()
        for i in range(4):
            b.set_bits(np.arange(i, 4096, 3, dtype=np.int64))
        assert c.memstat.live_bytes() == live0
        assert c.memstat.peak_bytes() == peak0  # no transient ledger spike
        v = c.memstat.verify(c._store, be)
        assert v["drift_bytes"] == 0 and not v["mismatched"]
        for _ in range(100):  # completer decrements after futures resolve
            if be.scratch_bytes()["delta_scratch"] == 0:
                break
            time.sleep(0.01)
        assert be.scratch_bytes()["delta_scratch"] == 0
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# derived metrics
# ---------------------------------------------------------------------------


def test_ingest_stats_derived_window_metrics():
    # Pure-arithmetic check through a real backend instance.
    c = _mk("scatter")
    try:
        sk = _backend(c)
        sk.counters["delta_runs"] = 3
        sk.counters["tape_runs"] = 1
        sk.counters["window_launches"] = 13
        sk.counters["launch_us"] = 800.0
        stats = sk.ingest_stats()
        assert stats["launches_per_window"] == 13 / 4
        assert stats["launch_us_per_window"] == 200.0
    finally:
        c.shutdown()
