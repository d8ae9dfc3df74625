"""Multi-target HLL bank inserts (engine._bank_add) against a numpy oracle.

Each entry point takes per-key slots into the run's distinct rows `urows`
and updates only those rows of the donated bank. The oracle is the plain
semantics: every valid key max-folds its rank into register
(row, bucket) of the bank, and changed_rows[r] says whether row r's
registers differ from before. Bank state and changed_rows must be
bit-identical to it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from redisson_tpu import engine
from redisson_tpu.client import RedissonTPU
from redisson_tpu.ops import hll
from redisson_tpu.ops.u64 import U64

M = hll.M
SEED = 0


@jax.jit
def _bucket_rank_u64(hi, lo):
    return hll.bucket_rank(engine._hll_h1_u64(U64(hi, lo), SEED, "m3"))


def _split(keys):
    return ((keys >> np.uint64(32)).astype(np.uint32),
            (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _oracle(bank, keys, rows, valid):
    """(new_bank, changed_rows) of inserting `keys` into bank rows `rows`
    (per key), skipping lanes where `valid` is false. Byte keys are the
    8-byte little-endian images of `keys`, which hash as the u64 keys."""
    bucket, rank = (np.asarray(a) for a in _bucket_rank_u64(*_split(keys)))
    new = bank.copy()
    np.maximum.at(new, (rows[valid], bucket[valid]), rank[valid])
    return new, np.any(new != bank, axis=1)


def _route(rows, capacity):
    """(slots, padded urows) of a run as the backend builds them: slot =
    index of the key's row in the run's distinct rows, in first-seen order;
    urows padded to a power of two in [MIN_BLOCK_ROWS, capacity] by
    repeating the first."""
    slot_of = {}
    slots = np.array([slot_of.setdefault(int(r), len(slot_of)) for r in rows],
                     np.int32)
    urows = engine.pad_rows_repeat(
        np.fromiter(slot_of, np.int32, len(slot_of)),
        floor=engine.MIN_BLOCK_ROWS, cap=capacity)
    return slots, urows


def _launch(entry, bank, keys, slots, urows, valid):
    """One launch of `entry` over a bucket-padded batch whose lanes beyond
    `valid` hold garbage keys, routed to slot 1 (a real row of every
    case), and rank 0 must keep them from changing it."""
    b = engine.bucket_size(keys.shape[0])
    n = int(valid.sum())
    pad = b - keys.shape[0]
    garbage = np.arange(1, pad + 1, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    keys = np.concatenate([keys, garbage])
    slots = np.concatenate([slots, np.ones((pad,), np.int32)])
    valid = np.concatenate([valid, np.zeros((pad,), bool)])
    bank = jnp.asarray(bank)
    if entry == "packed":
        # count-masked: every valid lane comes first
        assert valid[:n].all()
        packed = keys.astype("<u8").view(np.uint32).reshape(-1, 2)
        return engine.hll_bank_add_packed_rows(
            bank, packed, slots, urows, np.int32(n), SEED, "m3")
    if entry == "u64":
        hi, lo = _split(keys)
        return engine.hll_bank_add_u64_rows(
            bank, hi, lo, slots, urows, valid, SEED, "m3")
    data = keys.astype("<u8").view(np.uint8).reshape(-1, 8)
    lengths = np.full((b,), 8, np.int32)
    return engine.hll_bank_add_bytes_rows(
        bank, data, lengths, slots, urows, valid, SEED, "m3")


def _keys(rng, n):
    return rng.integers(1, 2 ** 63, size=n, dtype=np.uint64)


def _case(name, rng):
    """(capacity, initial bank, keys, per-key rows) of one case."""
    if name == "duplicate_keys":
        # the same keys twice per row, and more keys than buckets can keep
        # apart: (row, bucket) pairs repeat with equal and unequal ranks
        base = _keys(rng, 700)
        keys = np.concatenate([base, base, base[:300]])
        rows = np.concatenate([np.full(700, 3), np.full(700, 3),
                               np.full(300, 5)])
        return 16, np.zeros((16, M), np.int32), keys, rows
    if name == "padded_lanes":
        keys = _keys(rng, 300)
        rows = rng.choice([1, 6, 2], size=300)
        return 8, np.zeros((8, M), np.int32), keys, rows
    if name == "padding_repeats_changed_row":
        # 3 distinct rows, a 64-row block: 61 repeats of urows[0] = row 40,
        # whose own block row changes
        keys = _keys(rng, 900)
        rows = np.concatenate([np.full(300, 40), np.full(300, 7),
                               np.full(300, 99)])
        bank = np.zeros((128, M), np.int32)
        bank[40, :100] = 3
        return 128, bank, keys, rows
    if name == "every_row":
        keys = _keys(rng, 2000)
        rows = np.arange(2000) % 8
        return 8, np.zeros((8, M), np.int32), keys, rows
    if name == "no_change":
        keys = _keys(rng, 500)
        rows = rng.choice([0, 9, 33], size=500)
        bank, _ = _oracle(np.zeros((48, M), np.int32), keys, rows,
                          np.ones(500, bool))
        return 48, bank, keys, rows
    if name == "chunks":
        keys = _keys(rng, 3 * engine.MIN_BUCKET + 17)
        rows = rng.choice([12, 0, 30, 7], size=keys.shape[0])
        return 32, np.zeros((32, M), np.int32), keys, rows
    raise AssertionError(name)


ENTRIES = ["bytes", "u64", "packed"]
CASES = ["duplicate_keys", "padded_lanes", "padding_repeats_changed_row",
         "every_row", "no_change", "chunks"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("entry", ENTRIES)
def test_bank_add_rows_matches_oracle(entry, case, monkeypatch):
    rng = np.random.default_rng([ENTRIES.index(entry), CASES.index(case)])
    capacity, bank0, keys, rows = _case(case, rng)
    valid = np.ones(keys.shape[0], bool)
    want_bank, want_changed = _oracle(bank0, keys, rows, valid)
    slots, urows = _route(rows, capacity)
    if case == "chunks":
        # one run over several launches, as backend_tpu loops chunk_spans
        monkeypatch.setattr(engine, "MAX_BUCKET", engine.MIN_BUCKET)
        spans = engine.chunk_spans(keys.shape[0])
        assert len(spans) == 4
    else:
        spans = [(0, keys.shape[0])]
    bank, changed = bank0, np.zeros((capacity,), bool)
    for s, e in spans:
        bank, ch = _launch(entry, bank, keys[s:e], slots[s:e], urows,
                           valid[s:e])
        changed |= np.asarray(ch)
    np.testing.assert_array_equal(np.asarray(bank), want_bank)
    np.testing.assert_array_equal(changed, want_changed)
    if case == "no_change":
        assert not changed.any()
    if case == "every_row":
        assert urows.shape[0] == capacity and changed.all()
    if case == "padding_repeats_changed_row":
        assert urows.shape[0] == engine.MIN_BLOCK_ROWS
        assert (urows[3:] == 40).all() and changed[40]


def test_bank_add_rows_updates_bank_in_place():
    """At the served bank's shape ([65536, 16384] int32, 4 GiB) the program
    aliases the donated bank to its output and needs temporaries of the
    touched rows only, not a bank-sized copy. Compiled for abstract
    shapes: nothing is allocated."""
    s, m, b, r = 65536, M, engine.MIN_BUCKET, engine.MIN_BLOCK_ROWS
    sd = jax.ShapeDtypeStruct
    compiled = engine.hll_bank_add_bytes_rows.lower(
        sd((s, m), jnp.int32), sd((b, 16), jnp.uint8), sd((b,), jnp.int32),
        sd((b,), jnp.int32), sd((r,), jnp.int32), sd((b,), jnp.bool_),
    ).compile()
    bank_bytes = s * m * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < bank_bytes // 16, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= bank_bytes
    assert "input_output_alias={ {0}: (0, {}" in compiled.as_text()


@pytest.mark.parametrize("fmt", ["bytes", "ints"])
def test_coalesced_run_counts_touched_rows(fmt):
    """A cross-sketch batch through the facade goes through one
    multi-target launch that touches each target's row once, and gives the
    same registers and PFADD replies as per-target adds."""
    targets = [f"tr:{i}" for i in range(5)]
    per = {t: np.arange(i * 1000 + 1, i * 1000 + 41, dtype=np.uint64)
           for i, t in enumerate(targets)}

    def add(batch, t, keys):
        h = batch.get_hyper_log_log(t)
        if fmt == "ints":
            return h.add_ints_async(keys)
        return h.add_all_async([b"%d" % k for k in keys])

    coalesced, single = RedissonTPU.create(), RedissonTPU.create()
    try:
        for t in targets[:2]:  # already there: these replies are False
            for c in (coalesced, single):
                b = c.create_batch()
                add(b, t, per[t])
                b.execute()
        back = coalesced._routing.sketch
        launches0 = back.counters["bank_add_launches"]
        rows0 = back.counters["bank_add_rows"]
        b = coalesced.create_batch()
        futs = [add(b, t, per[t]) for t in targets]
        b.execute()
        assert back.counters["bank_add_launches"] - launches0 == 1
        assert back.counters["bank_add_rows"] - rows0 == len(targets)
        assert [f.result() for f in futs] == [False, False, True, True, True]
        for t in targets:
            b = single.create_batch()
            add(b, t, per[t])
            b.execute()
        sb = single._routing.sketch
        got = np.asarray(back.bank)
        want = np.asarray(sb.bank)
        for t in targets:
            np.testing.assert_array_equal(got[back._rows[t]],
                                          want[sb._rows[t]])
    finally:
        coalesced.shutdown()
        single.shutdown()


@pytest.mark.parametrize("backend,value", [
    ({"bank_add_rows": 300, "bank_add_launches": 75, "d2h_wait_ns": 1}, 4.0),
    ({"bank_add_rows": 0, "bank_add_launches": 0}, None),
    ({"d2h_wait_ns": 5, "runs_materialized": 2}, None),  # no such counters
])
def test_rows_per_launch_reader(backend, value):
    from perfbench import spec

    reader = spec.load_module("layers", "hll_bank_add.rows_per_launch")
    assert reader.read({"delta": {"backend": backend}}) == value
