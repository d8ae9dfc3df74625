"""Mesh data plane (PR 19): N logical shards served by ONE engine stack
over a device mesh (`data_plane="mesh"`), instead of N Python engine
stacks (`data_plane="stacks"`).

Coverage:

1. collective kernels — shard_map/pmax PFMERGE, count and occupancy over
   a mesh-sharded bank are bit-identical to a host-fold oracle and to the
   single-device stacks kernels;
2. mode parity — a randomized mixed-kind multi-shard workload produces
   bit-identical per-op results AND raw register/cell state between
   data_plane="stacks" and data_plane="mesh";
3. live migration — slots move between logical shards in mesh mode under
   concurrent writers with ZERO lost acks (tools/histcheck verdict), and
   bank rows relocate device-side with their counts preserved;
4. mesh cache — repeated reshards onto an unchanged device set reuse the
   cached Mesh (no rebuild per call: the topology on_change fix);
5. churn + memstat — randomized create/delete/migrate churn on the mesh
   bank keeps the per-(shard, kind) ledger rollups exact (zero drift).
"""

import random
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from redisson_tpu import engine
from redisson_tpu.client import RedissonTPU
from redisson_tpu.config import Config
from redisson_tpu.ops import hll
from redisson_tpu.ops.crc16 import key_slot
from redisson_tpu.parallel import mesh as mesh_mod
from tools import histcheck


# ---------------------------------------------------------------------------
# 1. collective kernels vs host-fold oracle


def _mesh_bank(capacity=64, num_shards=4, seed=3):
    mesh = mesh_mod.get_mesh(axis=mesh_mod.SLOT_AXIS)
    sb = mesh_mod.ShardedBank(mesh, capacity, num_shards)
    host = np.random.default_rng(seed).integers(
        0, 52, size=(sb.capacity, hll.M), dtype=np.int32)
    return mesh, sb, host


def test_collective_merge_matches_host_fold_oracle():
    mesh, sb, host = _mesh_bank()
    rows = [3, 17, 33, 60, 9]  # span several device blocks; includes target
    target = 9
    bank = jax.device_put(host, sb.sharding)
    got = np.asarray(engine.hll_bank_merge_rows_collective(
        bank, jnp.asarray(rows, jnp.int32), jnp.int32(target), mesh=mesh))
    oracle = host.copy()
    oracle[target] = host[rows].max(axis=0)
    assert (got == oracle).all()


def test_collective_merge_count_matches_stacks_kernel():
    mesh, sb, host = _mesh_bank(seed=5)
    rows = [0, 21, 42, 63, 11]
    target = 11
    bank = jax.device_put(host, sb.sharding)
    new_bank, cnt = engine.hll_bank_merge_count_rows_collective(
        bank, jnp.asarray(rows, jnp.int32), jnp.int32(target), mesh=mesh)
    # Stacks oracle: the same merge+count through the single-device kernel.
    dev = jax.devices("cpu")[0]
    sbank = jax.device_put(host, dev)
    sbank2, scnt = engine.hll_bank_merge_count_rows(
        sbank, jnp.asarray(rows, jnp.int32), jnp.int32(target))
    assert (np.asarray(new_bank) == np.asarray(sbank2)).all()
    assert int(cnt) == int(scnt)


def test_collective_count_and_occupancy_match_oracles():
    mesh, sb, host = _mesh_bank(seed=7)
    rows = [1, 30, 55]
    bank = jax.device_put(host, sb.sharding)
    cnt = int(engine.hll_bank_count_rows_collective(
        bank, jnp.asarray(rows, jnp.int32), mesh=mesh))
    dev = jax.devices("cpu")[0]
    scnt = int(engine.hll_bank_count_rows(
        jax.device_put(host, dev), jnp.asarray(rows, jnp.int32)))
    assert cnt == scnt

    # occupancy: zero a few rows, count the non-empty remainder
    host2 = host.copy()
    host2[5] = 0
    host2[40] = 0
    occ = int(engine.hll_bank_occupancy_collective(
        jax.device_put(host2, sb.sharding), mesh=mesh))
    assert occ == int(np.sum(np.any(host2 != 0, axis=1)))


# ---------------------------------------------------------------------------
# 2. mode parity: randomized mixed-kind multi-shard windows


def _mesh_cluster(tmp_path, data_plane, sub="cl"):
    cfg = Config()
    cfg.use_cluster(num_shards=4, dir=str(tmp_path / f"{sub}-{data_plane}"),
                    data_plane=data_plane)
    return RedissonTPU.create(cfg)


def _mixed_workload(c, n_vals=300, seed=0xA11CE):
    """Deterministic randomized mixed-kind workload across all shards;
    returns the per-op result list."""
    rng = random.Random(seed)
    results = []
    f = c.get_bloom_filter("tm:bloom")
    f.try_init(expected_insertions=20_000, false_probability=0.01)
    for rnd in range(2):
        for i in range(6):
            h = c.get_hyper_log_log(f"tm:h{i}")
            h.add_all([b"r%d:%d:%d" % (rnd, i, rng.randrange(1 << 40))
                       for _ in range(n_vals)])
            results.append(("pfcount", i, h.count()))
        for i in range(4):
            bs = c.get_bit_set(f"tm:b{i}")
            bs.set_bits([rng.randrange(1 << 14) for _ in range(32)])
            results.append(("bitcount", i, int(bs.cardinality())))
        added = f.add_all([b"f%d:%d" % (rnd, rng.randrange(1 << 30))
                           for _ in range(100)])
        results.append(("bfadd", rnd, int(np.sum(added))))
    # cross-shard merges exercise the collective path in mesh mode
    results.append(("pfmerge", 0,
                    c.get_hyper_log_log("tm:h0").merge_with_and_count(
                        "tm:h1", "tm:h2")))
    results.append(("pfcountw", 0,
                    c.get_hyper_log_log("tm:h3").count_with("tm:h4")))
    return results


def _state_digest(c):
    """Raw observable state through the facade: HLL registers + bit cells."""
    router = c.cluster.router
    out = {}
    for i in range(6):
        name = f"tm:h{i}"
        exported = router.execute_sync(name, "hll_export", None)
        out[name] = np.asarray(exported[0]).tobytes()
    for name in [f"tm:b{i}" for i in range(4)] + ["tm:bloom"]:
        exported = router.execute_sync(name, "bits_export", None)
        out[name] = np.asarray(exported[1]).tobytes()
    return out


def test_mode_parity_randomized_multi_shard_windows(tmp_path):
    c = _mesh_cluster(tmp_path, "stacks")
    try:
        res_stacks = _mixed_workload(c)
        dig_stacks = _state_digest(c)
    finally:
        c.shutdown()
    c = _mesh_cluster(tmp_path, "mesh")
    try:
        res_mesh = _mixed_workload(c)
        dig_mesh = _state_digest(c)
        backend = c.cluster.mesh_client._routing.sketch
        assert backend.counters["collective_merges"] >= 1
    finally:
        c.shutdown()
    assert res_stacks == res_mesh
    assert dig_stacks == dig_mesh


# ---------------------------------------------------------------------------
# 3. live migration in mesh mode: zero lost acks + device-side row moves


def test_mesh_live_migration_zero_lost_acks(tmp_path):
    c = _mesh_cluster(tmp_path, "mesh", sub="mig")
    try:
        mgr = c.cluster
        table = mgr.router.slot_table()

        # keys pinned to shard 0 so one migration covers them all
        keys, i = [], 0
        while len(keys) < 12:
            k = f"mg{i}"
            if table[key_slot(k)] == 0:
                keys.append(k)
            i += 1
        hll_keys, i = [], 0
        while len(hll_keys) < 2:
            k = f"mh{i}"
            if table[key_slot(k)] == 0:
                hll_keys.append(k)
            i += 1
        for k in keys:
            c.get_bucket(k).set("v0")
        counts_before = {}
        for k in hll_keys:
            h = c.get_hyper_log_log(k)
            h.add_all([b"%s:%d" % (k.encode(), v) for v in range(500)])
            counts_before[k] = h.count()
        move = sorted({key_slot(k) for k in keys + hll_keys})

        rec = histcheck.HistoryRecorder()
        stop = threading.Event()
        # Two writers over DISJOINT key halves (one writer per key, so
        # per-key ack order is real-time order); logical seqs — lost-ack
        # checking needs order only.
        def writer(tenant, my_keys):
            rng = random.Random(hash(tenant) & 0xFFFF)
            seq = 0
            n = 0
            while not stop.is_set():
                k = my_keys[n % len(my_keys)]
                v = f"{tenant}:{n}"
                try:
                    c.get_bucket(k).set(v)
                    seq += 1
                    rec.record_write(tenant, k, v, acked_seq=seq)
                except Exception:  # noqa: BLE001 — fate unknown under the fence
                    rec.record_write_unknown(tenant, k, v)
                n += 1

        threads = [
            threading.Thread(target=writer, args=("wa", keys[:6]),
                             daemon=True),
            threading.Thread(target=writer, args=("wb", keys[6:]),
                             daemon=True),
        ]
        for t in threads:
            t.start()
        try:
            stats = mgr.migrate_slots(move, 2, timeout_s=120)
        finally:
            stop.set()
            for t in threads:
                t.join(10)

        post = mgr.router.slot_table()
        assert all(post[s] == 2 for s in move)
        # device-side bank-row relocation carried the HLL rows
        assert stats.get("bank_rows_relocated", 0) >= len(hll_keys)
        for k in hll_keys:
            assert c.get_hyper_log_log(k).count() == counts_before[k]

        final = {k: c.get_bucket(k).get() for k in keys}
        v = histcheck.check(rec, final_state=final)
        assert rec.acked_count() > 0
        assert v.ok, v.issues
        assert v.lost_acks == 0
    finally:
        c.shutdown()


# ---------------------------------------------------------------------------
# 4. mesh cache: reshard onto an unchanged device set never rebuilds


def test_mesh_cache_pinned_across_repeated_reshards():
    m1 = mesh_mod.get_mesh(4)
    s0 = mesh_mod.mesh_cache_stats()
    assert mesh_mod.get_mesh(4) is m1
    s1 = mesh_mod.mesh_cache_stats()
    assert s1["builds"] == s0["builds"]
    assert s1["hits"] == s0["hits"] + 1

    cfg = Config()
    pod_cfg = cfg.use_pod()
    pod_cfg.bank_capacity = 16
    pod = RedissonTPU.create(cfg)
    try:
        backend = pod._pod_backend()
        assert backend is not None
        h = pod.get_hyper_log_log("mc:h")
        h.add_all([b"v%d" % i for i in range(50)])
        before = h.count()
        ndev = int(backend.mesh.devices.size)
        builds0 = mesh_mod.mesh_cache_stats()["builds"]
        for _ in range(5):
            # topology on_change with an UNCHANGED device set: cached Mesh,
            # zero rebuilds (the recompile-hazard fix this test pins)
            backend.reshard(ndev)
        assert mesh_mod.mesh_cache_stats()["builds"] == builds0
        assert backend.mesh is mesh_mod.get_mesh(ndev)
        assert h.count() == before  # state survived the reshards
    finally:
        pod.shutdown()


# ---------------------------------------------------------------------------
# 5. churn property: mesh bank accounting stays exact


def test_mesh_bank_churn_memstat_exact(tmp_path):
    c = _mesh_cluster(tmp_path, "mesh", sub="churn")
    try:
        mgr = c.cluster
        mc = mgr.mesh_client
        rng = random.Random(0xBEEF)
        live = set()
        for step in range(40):
            roll = rng.random()
            if roll < 0.5:
                name = "ch:h%d" % rng.randrange(10)
                c.get_hyper_log_log(name).add(b"v%d" % step)
                live.add(name)
            elif roll < 0.7:
                name = "ch:b%d" % rng.randrange(4)
                c.get_bit_set(name).set(rng.randrange(2048))
            elif live:
                name = live.pop()
                c.delete(name)
            if step % 10 == 9:
                v = mc.memory_verify()
                assert v["ok"], (step, v)
        # migration-driven relocation churns row placement too
        table = mgr.router.slot_table()
        move = sorted({key_slot(n) for n in live
                       if table[key_slot(n)] != 1})[:8]
        if move:
            mgr.migrate_slots(move, 1, timeout_s=120)
        v = mc.memory_verify()
        assert v["ok"] and v["drift_bytes"] == 0, v
        # per-shard rollups sum exactly to the bank allocation
        acct = mc.memstat
        st = mc.memory_stats()
        assert st["bank.bytes"] == acct.bank_bytes()
        backend = mc._routing.sketch
        assert acct.bank_bytes() == int(backend.bank.nbytes)
    finally:
        c.shutdown()
