"""Delta window retire: every folded plane of a pipeline window merges in
one fused launch (engine.delta_merge_stack), split into chunks only past
the stack budget.

Layers:

1. Kernel — pallas_kernels.delta_merge (interpret mode) vs the engine's
   XLA merge vs a numpy oracle: bit-identical merged rows + changed
   flags on randomized stacks of HLL register rows and bit-cell rows;
   zero pad rows merge as identity.
2. Property — randomized mixed hll/bloom/bitset windows through the
   real client with ingest="delta" vs the serial scatter oracle:
   per-op results (PFADD changed, bloom newly incl. intra-window
   duplicates, bitset old-bit reads) and the engine digest must be
   bit-identical — including under kernel_launch fault injection with
   serve retries absorbing the injected merge fault.
3. Satellites — a window over the stack budget splits into chunks and
   stays correct; per-chunk failure isolation (a failed chunk commits
   nothing, other chunks commit and bump epochs); donation in the merge
   kernels + the memstat-ledger no-spike contract.
"""

import time

import numpy as np
import pytest

from redisson_tpu import native
from redisson_tpu.client import RedissonTPU
from redisson_tpu.config import Config, TpuConfig
from redisson_tpu.fault import inject
from redisson_tpu.fault.taxonomy import RetryableFault

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


def _random_stacks(rng, t, lanes):
    """Randomized [t, lanes] old/delta stacks — HLL register rows (0..64),
    bit-cell rows (0/1) and all-zero pad rows, each valid over a random
    prefix as a padded plane is — plus the numpy-oracle outputs."""
    old = np.zeros((t, lanes), np.uint8)
    delta = np.zeros((t, lanes), np.uint8)
    for i in range(t):
        kind = rng.choice(["hll", "bits", "pad"])
        if kind == "pad":
            continue
        top = 65 if kind == "hll" else 2
        cells = int(rng.integers(1, lanes + 1))
        old[i, :cells] = rng.integers(0, top, cells, np.uint8)
        delta[i, :cells] = rng.integers(0, top, cells, np.uint8)
    want = np.maximum(old, delta)
    return old, delta, want, (want != old).any(axis=1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_delta_merge_interpret_lax_oracle_identical(seed):
    import jax.numpy as jnp

    from redisson_tpu import engine
    from redisson_tpu.ops import pallas_kernels as pk

    old, delta, want, want_changed = _random_stacks(
        np.random.default_rng(seed), 8, 2048)
    # Both merges donate their old stack: each gets its own copies.
    m_lax, c_lax = engine.delta_merge_stack(jnp.asarray(old),
                                            jnp.asarray(delta))
    np.testing.assert_array_equal(np.asarray(m_lax), want)
    np.testing.assert_array_equal(np.asarray(c_lax), want_changed)
    m_pl, c_pl = pk.delta_merge(jnp.asarray(old), jnp.asarray(delta),
                                block=512, interpret=True)
    np.testing.assert_array_equal(np.asarray(m_pl), want)
    np.testing.assert_array_equal(np.asarray(c_pl), want_changed)


def test_delta_merge_stack_pad_rows_are_identity():
    """The zero rows a window pads its stack with (to a power of two)
    leave their old rows as they are and report no change."""
    import jax.numpy as jnp

    from redisson_tpu import engine
    from redisson_tpu.ops import pallas_kernels as pk

    old = np.full((2, 1024), 7, np.uint8)
    zero = np.zeros((2, 1024), np.uint8)
    for merge in (engine.delta_merge_stack,
                  lambda o, d: pk.delta_merge(o, d, interpret=True)):
        merged, changed = merge(jnp.asarray(old), jnp.asarray(zero))
        np.testing.assert_array_equal(np.asarray(merged), old)
        assert not np.asarray(changed).any()


# ---------------------------------------------------------------------------
# 2. property: delta vs serial scatter oracle, with and without faults
# ---------------------------------------------------------------------------


def _play_workload(c, rng, sync, disjoint=False):
    """One randomized mixed workload; returns every per-op result. Same
    rng seed -> identical op stream, so delta and oracle see the same
    submissions in the same order. `sync` submits op-by-op (the serial
    oracle); async submits each round as one burst (one delta window of
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
        # (test_delta_intra_batch_bloom_duplicates_are_serial).
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
def test_delta_window_matches_serial_scatter_oracle(seed):
    ct, cs = _mk("delta"), _mk("scatter")
    try:
        res_t = _play_workload(ct, np.random.default_rng(seed), sync=False)
        res_s = _play_workload(cs, np.random.default_rng(seed), sync=True)
        assert res_t == res_s
        assert _digest(ct) == _digest(cs)
        stats = _backend(ct).ingest_stats()
        assert stats["delta_runs"] >= 1
        # every window fit one chunk: one merge launch a window
        assert stats["merge_launches"] == stats["delta_runs"]
    finally:
        ct.shutdown()
        cs.shutdown()


@needs_native
def test_delta_intra_batch_bloom_duplicates_are_serial():
    """Duplicates INSIDE one bloom op fold serially (key i sees keys < i
    of its own batch), matching one-key-at-a-time semantics."""
    c = _mk("delta")
    try:
        f = c.get_bloom_filter("dup:bloom")
        f.try_init(expected_insertions=10_000, false_probability=0.01)
        got = np.asarray(f.add_ints(np.array([11, 22, 11], np.uint64)))
        assert got[0] and got[1] and not got[2]
    finally:
        c.shutdown()


@needs_native
def test_delta_window_fault_injection_retries_to_oracle_state():
    """An injected kernel_launch fault at the merge seam fires BEFORE the
    chunk commits anything, so serve retries replay the ops and the
    final state + per-op results stay bit-identical to the fault-free
    serial oracle."""
    plan = [{"seam": "kernel_launch", "kind": "delta_merge", "nth": 1},
            {"seam": "kernel_launch", "kind": "delta_merge", "nth": 3}]
    ct, cs = _mk("delta", plan=plan), _mk("scatter")
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
def test_delta_fault_without_retry_fails_window_whole():
    """No serve tier: the injected merge fault surfaces on EVERY op of a
    one-chunk window (the chunk is the failure unit) and nothing commits
    — the bank row stays empty and the store objects keep their
    pre-window state."""
    c = _mk("delta")
    try:
        inject.install(inject.FaultInjector(inject.FaultPlan(rules=[
            inject.FaultRule(seam="kernel_launch", kind="delta_merge",
                             nth=1)])))
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
        # Retry after the fault: clean state, normal delta retire.
        inject.uninstall()
        assert c.get_hyper_log_log("tf:h").add_ints(
            np.arange(2000, dtype=np.uint64)) is True
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# 3a. a window over the stack budget splits into chunks
# ---------------------------------------------------------------------------


@needs_native
def test_delta_overflow_splits_into_chunks_and_stays_correct():
    """A window whose planes overflow one merge stack retires through
    several chunked launches — each bitset chunk with its pre-merge pack
    — and stays bit-identical to the oracle."""
    c, cs = _mk("delta"), _mk("scatter")
    try:
        be = _backend(c)
        # Budget below one 16K-lane HLL plane: any window holding an HLL
        # plane and another plane needs two chunks.
        be.DELTA_STACK_CELLS = 1 << 13
        for cl in (c, cs):
            b = cl.get_bit_set("ov:bits")
            first = np.asarray(b.set_bits([3, 9, 3000]))
            np.testing.assert_array_equal(first, [False, False, False])
        runs0 = be.counters["delta_runs"]
        launches0 = be.counters["merge_launches"]
        # Mixed hll+bitset burst -> ONE window of two chunks: the old-bit
        # reads still see pre-window state.
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
        runs = be.counters["delta_runs"] - runs0
        launches = be.counters["merge_launches"] - launches0
        assert launches > runs >= 1  # some window split into chunks
        np.testing.assert_array_equal(
            np.asarray(be.store.get("ov:bits").state),
            np.asarray(_backend(cs).store.get("ov:bits").state))
        np.testing.assert_array_equal(
            np.asarray(be._ensure_bank())[be._rows["ov:h"]],
            np.asarray(_backend(cs)._ensure_bank())[
                _backend(cs)._rows["ov:h"]])
    finally:
        c.shutdown()
        cs.shutdown()


# ---------------------------------------------------------------------------
# 3b. per-chunk failure isolation in the chunked delta path
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
# 3c. donation + memstat-ledger no-spike
# ---------------------------------------------------------------------------


def test_merge_kernels_declare_donation():
    """delta_merge_stack / merge_stack donate their old
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


@needs_native
@pytest.mark.parametrize("ingest", ["delta"])
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
        sk.counters["delta_runs"] = 4
        sk.counters["window_launches"] = 13
        sk.counters["launch_us"] = 800.0
        stats = sk.ingest_stats()
        assert stats["launches_per_window"] == 13 / 4
        assert stats["launch_us_per_window"] == 200.0
    finally:
        c.shutdown()
