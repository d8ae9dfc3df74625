"""The mesh data plane's write program and bank placement, on the 8
virtual CPU devices conftest sets (a 4-device mesh of them where the
served cluster has four chips):

1. the multi-target insert (`hll_bank_add_bytes_rows`, engine._bank_add)
   with a 4-device mesh against the same program on one device:
   the same bank bit for bit and the same per-row `changed` flags, over
   rows on every device, padding repeats, duplicate keys, and rows beyond
   a doubling of the bank;
2. bank creation and growth on the mesh give a sharded bank;
3. shapes: the mesh write program traces at the served size (262,144 x
   16,384, a 16 GiB bank), and so do the flat-index-free write-backs of
   the hostfold and delta paths at 131,072 rows, where an int32
   index over the whole bank would pass 2^31;
4. after the warm-up's shapes, a window with a new distinct-row count
   builds no program;
5. PFADDs and 7-key unions through a mesh cluster at a small size give
   the registers and estimates of the plain reference
   (perfbench/ref/hll.py), also for a run split over several launches
   and for keys already on a device.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench.ref import hll as ref
from redisson_tpu import engine
from redisson_tpu.ops import hll
from redisson_tpu.parallel import mesh as mesh_mod

M = hll.M


@pytest.fixture(scope="module")
def mesh4():
    return mesh_mod.build_mesh(4, mesh_mod.SLOT_AXIS)


def _keys_bytes(keys):
    data = np.ascontiguousarray(np.asarray(keys, "<u8")).view(
        np.uint8).reshape(-1, 8)
    return data, np.full((data.shape[0],), 8, np.int32)


def _run(rng, rows_per_op, nkeys):
    """A coalesced run as the backend stages it: each op's keys, its slot
    among the run's distinct rows, those rows repeat-padded to a power of
    two of at least MIN_BLOCK_ROWS."""
    slot_of = {}
    slots = [slot_of.setdefault(r, len(slot_of)) for r in rows_per_op]
    urows = engine.pad_rows_repeat(
        np.fromiter(slot_of, np.int32, len(slot_of)),
        floor=engine.MIN_BLOCK_ROWS)
    keys = rng.integers(0, 1 << 27, size=(len(rows_per_op) * nkeys,),
                        dtype=np.uint64)
    data, lengths = _keys_bytes(keys)
    pdata, plengths, valid = engine.pad_bytes(data, lengths)
    pslot, _ = engine.pad_ints(np.repeat(np.asarray(slots, np.int32), nkeys))
    return pdata, plengths, pslot, urows, valid, slots, len(slot_of)


def _both(mesh, host_bank, run):
    """(bank, changed per distinct row) from the mesh program and from the
    one-device program, on the same bank and run."""
    pdata, plengths, pslot, urows, valid, slots, nrows = run
    sh = NamedSharding(mesh, P(mesh_mod.SLOT_AXIS, None))
    mbank, mchanged = engine.hll_bank_add_bytes_rows(
        jax.device_put(host_bank, sh), pdata, plengths, pslot, urows, valid,
        mesh=mesh)
    one = jax.devices()[0]
    sbank, schanged = engine.hll_bank_add_bytes_rows(
        jax.device_put(host_bank, one), pdata, plengths, pslot, urows, valid)
    assert mbank.sharding.is_equivalent_to(sh, 2)
    first = np.asarray(urows)[:nrows]
    return (np.asarray(mbank), np.asarray(mchanged)[:nrows],
            np.asarray(sbank), np.asarray(schanged)[first])


@pytest.mark.parametrize("case", ["every_device", "padding", "duplicates"])
def test_mesh_add_matches_one_device_insert(mesh4, case):
    rng = np.random.default_rng({"every_device": 1, "padding": 2,
                                 "duplicates": 3}[case])
    cap = 256  # 64 rows a device
    host = rng.integers(0, 8, size=(cap, M), dtype=np.int32)
    if case == "every_device":
        # 70 distinct rows over all four devices: a 128-row block
        rows = list(rng.choice(cap, size=70, replace=False))
    elif case == "padding":
        # 3 rows, padded to 64 with repeats of the first
        rows = [5, 130, 255]
    else:
        # several ops on one row, and keys repeated within an op
        rows = [7, 7, 200, 7, 64, 200]
    run = _run(rng, rows, 32)
    if case == "duplicates":
        pdata = run[0].copy()
        pdata[1:32] = pdata[0]
        run = (pdata,) + run[1:]
    mbank, mchanged, sbank, schanged = _both(mesh4, host, run)
    assert (mbank == sbank).all()
    assert (mchanged == schanged).all()
    assert mchanged.any()
    # a run that changes nothing reports nothing
    mbank2, mchanged2, _, _ = _both(mesh4, mbank, run)
    assert (mbank2 == mbank).all() and not mchanged2.any()


def test_mesh_add_rows_beyond_a_doubling(mesh4):
    rng = np.random.default_rng(4)
    sb = mesh_mod.ShardedBank(mesh4, 128, 4)
    host = rng.integers(0, 8, size=(128, M), dtype=np.int32)
    bank = engine.hll_bank_grow_mesh(jax.device_put(host, sb.sharding), 256,
                                     mesh=mesh4)
    grown = np.zeros((256, M), np.int32)
    grown[:128] = host
    assert (np.asarray(bank) == grown).all()
    run = _run(rng, [3, 127, 128, 200, 255], 40)
    mbank, mchanged, sbank, schanged = _both(mesh4, grown, run)
    assert (mbank == sbank).all()
    assert (mchanged == schanged).all()
    assert (mbank[128:] != 0).any()


def test_mesh_bank_is_made_and_grown_sharded(mesh4):
    sb = mesh_mod.ShardedBank(mesh4, 64, 4)
    bank = sb.make(M)
    assert bank.shape == (64, M)
    assert bank.sharding.is_equivalent_to(sb.sharding, 2)
    assert len(bank.addressable_shards) == 4
    assert {s.data.shape for s in bank.addressable_shards} == {(16, M)}
    bank = bank.at[63].set(5)
    grown = engine.hll_bank_grow_mesh(bank, 128, mesh=mesh4)
    assert grown.sharding.is_equivalent_to(sb.sharding, 2)
    assert {s.data.shape for s in grown.addressable_shards} == {(32, M)}
    host = np.asarray(grown)
    assert (host[63] == 5).all() and not host[64:].any()
    assert int(np.count_nonzero(host)) == M


def test_mesh_cluster_bank_is_sharded():
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_cluster(num_shards=4, data_plane="mesh")
    c = RedissonTPU.create(cfg)
    try:
        for i in range(600):  # past the first capacity: the bank grows
            c.get_hyper_log_log(f"sh:{i}").add_ints(
                np.arange(i, i + 3, dtype=np.uint64))
        be = c.cluster.mesh_client._routing.sketch
        assert be.bank.shape[0] >= 600
        assert be.bank.sharding.is_equivalent_to(
            be._sharded_bank.sharding, 2)
        assert be.counters["mesh_add_launches"] >= 1
        assert be.counters["mesh_add_block_rows"] >= \
            be.counters["mesh_add_rows"] >= 1
    finally:
        c.shutdown()


def test_mesh_add_traces_at_the_served_size(mesh4):
    sh = NamedSharding(mesh4, P(mesh_mod.SLOT_AXIS, None))
    bank = jax.ShapeDtypeStruct((262144, M), jnp.int32, sharding=sh)
    n, r = 65536, 256
    out, changed = jax.eval_shape(
        lambda b, d, n_, s, u, v: engine.hll_bank_add_bytes_rows(
            b, d, n_, s, u, v, mesh=mesh4),
        bank, jax.ShapeDtypeStruct((n, 8), jnp.uint8),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((r,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.bool_))
    assert out.shape == (262144, M) and changed.shape == (r,)
    grown = jax.eval_shape(
        lambda b: engine.hll_bank_grow_mesh(b, 524288, mesh=mesh4), bank)
    assert grown.shape == (524288, M)


@pytest.mark.parametrize("program", ["absorb_rows", "set_rows"])
def test_row_write_backs_trace_past_an_int32_flat_index(program):
    bank = jax.ShapeDtypeStruct((131072, M), jnp.int32)
    rows = jax.ShapeDtypeStruct((8,), jnp.int32)
    regs = jax.ShapeDtypeStruct((8, M), jnp.uint8)
    if program == "absorb_rows":
        out, changed = jax.eval_shape(engine.hll_bank_absorb_rows, bank,
                                      regs, rows)
        assert changed.shape == (8,)
    else:
        out = jax.eval_shape(engine.hll_bank_set_rows, bank, regs, rows)
    assert out.shape == (131072, M)


def test_new_row_count_builds_no_program_after_warmup(mesh4):
    """Distinct-row counts map onto the power-of-two block classes (floor
    MIN_BLOCK_ROWS), so once the warm-up met a class, any count in it
    reuses the program."""
    compiles = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on)
    rng = np.random.default_rng(6)
    sb = mesh_mod.ShardedBank(mesh4, 512, 4)
    bank = sb.make(M)

    def window(nrows):
        rows = rng.choice(512, size=nrows, replace=False)
        pdata, plengths, pslot, urows, valid, _, _ = _run(rng, rows, 4)
        return engine.hll_bank_add_bytes_rows(bank, pdata, plengths, pslot,
                                             urows, valid, mesh=mesh4)

    bank, _ = window(40)    # the warm-up: a 64-row block ...
    bank, _ = window(100)   # ... and a 128-row block
    jax.block_until_ready(bank)
    seen = len(compiles)
    bank, _ = window(50)    # new counts, shapes the warm-up built
    bank, _ = window(120)
    jax.block_until_ready(bank)
    assert compiles[seen:] == []


def test_mesh_cluster_pfadd_and_unions_match_reference():
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_cluster(num_shards=4, data_plane="mesh")
    c = RedissonTPU.create(cfg)
    rng = np.random.default_rng(7)
    pages, days = 6, 7
    truth = {}
    try:
        for p in range(pages):
            for d in range(days):
                key = f"d{d}:{p}"
                ids = rng.integers(0, 1 << 27, size=(300 + 50 * d,),
                                   dtype=np.uint64)
                data, _ = _keys_bytes(ids)
                # byte elements, as the wire's PFADD sends them
                c.get_hyper_log_log(key).add_all(list(map(bytes, data)))
                truth[key] = ids
        be = c.cluster.mesh_client._routing.sketch
        assert be.counters["mesh_add_launches"] >= 1
        for key, ids in truth.items():
            regs = c.cluster.router.execute_sync(key, "hll_export", None)[0]
            assert (np.asarray(regs) == ref.hll_registers(ids)).all(), key
        for p in range(pages):
            names = [f"d{d}:{p}" for d in range(days)]
            got = c.get_hyper_log_log(names[0]).count_with(*names[1:])
            want = ref.estimate(ref.hll_registers(
                np.concatenate([truth[n] for n in names])))
            assert abs(got - want) <= max(1.0, 1e-4 * want), (p, got, want)
            one = c.get_hyper_log_log(names[3]).count()
            want1 = ref.estimate(ref.hll_registers(truth[names[3]]))
            assert abs(one - want1) <= max(1.0, 1e-4 * want1)
        assert be.counters["collective_merges"] >= pages
    finally:
        c.shutdown()


def test_mesh_run_past_the_launch_cap_splits_and_matches_reference(
        monkeypatch):
    """A run of more keys than MESH_CHUNK_KEYS retires in several launches
    over the same row block, with the registers of the reference."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    monkeypatch.setattr(engine, "MESH_CHUNK_KEYS", 1024)
    cfg = Config()
    cfg.use_cluster(num_shards=4, data_plane="mesh")
    c = RedissonTPU.create(cfg)
    rng = np.random.default_rng(8)
    try:
        be = c.cluster.mesh_client._routing.sketch
        ids = rng.integers(0, 1 << 27, size=(2800,), dtype=np.uint64)
        c.get_hyper_log_log("cap:0").add_all(list(map(bytes,
                                                      _keys_bytes(ids)[0])))
        assert be.counters["mesh_add_launches"] == 3  # 1,024 keys a launch
        regs = c.cluster.router.execute_sync("cap:0", "hll_export", None)[0]
        assert (np.asarray(regs) == ref.hll_registers(ids)).all()
    finally:
        c.shutdown()


def test_mesh_device_resident_keys_match_reference():
    """Keys already on a device (`add_device`) retire through the mesh
    write program too, replicated over the mesh."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_cluster(num_shards=4, data_plane="mesh")
    c = RedissonTPU.create(cfg)
    try:
        ids = np.random.default_rng(9).integers(0, 1 << 27, size=(1500,),
                                                dtype=np.uint64)
        packed = jnp.asarray(ids.view(np.uint32).reshape(-1, 2))
        assert c.get_hyper_log_log("dev:0").add_device(packed)
        be = c.cluster.mesh_client._routing.sketch
        assert be.counters["mesh_add_launches"] == 1
        regs = c.cluster.router.execute_sync("dev:0", "hll_export", None)[0]
        assert (np.asarray(regs) == ref.hll_registers(ids)).all()
    finally:
        c.shutdown()
