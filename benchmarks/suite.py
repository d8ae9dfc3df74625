#!/usr/bin/env python
"""The 5 BASELINE configs as a runnable suite.

Each config measures BOTH execution paths where meaningful:
  * `engine`  — the TPU-native path (this framework's device kernels);
  * `redis`   — the reference-modeled path (same object API over the
    embedded RESP server, standing in for `embedded redis`: every op a
    real wire round-trip, the reference's execution model).

Usage:
    python benchmarks/suite.py --config 1          # one config
    python benchmarks/suite.py --all               # everything
    python benchmarks/suite.py --all --publish     # + write BASELINE.json

Scale knobs default to CI-sized runs; --full uses the BASELINE sizes
(1B-key streaming etc. — hours on CPU, minutes on a real chip).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# No import-time backend touch: `--help` and the lint gate need no device.


_TINY = bool(os.environ.get("RTPU_BENCH_TINY"))

# Ingest path for the sketch backends ("auto" = the measured planner,
# redisson_tpu/ingest/planner.py); set once from --ingest in main().
_INGEST = "auto"


def _scale(n: int) -> int:
    """CI smoke scale: RTPU_BENCH_TINY=1 shrinks every size 100x."""
    return max(1000, n // 100) if _TINY else n


def _mkclient(mode: str):
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    cfg = Config()
    if mode == "redis":
        from redisson_tpu.interop.fake_server import EmbeddedRedis

        er = EmbeddedRedis()
        cfg.use_redis().address = f"redis://127.0.0.1:{er.port}"
        c = RedissonTPU.create(cfg)
        c._embedded = er  # keep alive; closed with the client
        return c
    cfg.use_tpu().ingest = _INGEST
    return RedissonTPU.create(cfg)


def _close(c):
    c.shutdown()
    er = getattr(c, "_embedded", None)
    if er is not None:
        er.stop()


def config1(full: bool):
    """Single-key PFADD/PFCOUNT — 1M string keys through the client facade."""
    n = _scale(1_000_000 if full else 200_000)
    keys = [b"user:%d" % i for i in range(n)]
    out = {}
    for mode in ("engine", "redis"):
        c = _mkclient(mode)
        try:
            # Warm kernels at the SAME shape and ingest path as the timed
            # run (a smaller batch would bucket differently and could take
            # the other hostfold/jit path), same policy as configs 3/5.
            # The redis path has no kernels to warm and slabs at 10k, so a
            # small warm covers its codec/setup without ~3s of untimed wire
            # traffic.
            wh = c.get_hyper_log_log("b1:warm")
            wh.add_all(keys if mode == "engine" else keys[:10_000])
            wh.count()
            h = c.get_hyper_log_log("b1:hll")
            t0 = time.perf_counter()
            if mode == "engine":
                h.add_all(keys)
            else:
                # the wire path pipelines adds in slabs, like RBatch would
                step = 10_000
                for i in range(0, n, step):
                    h.add_all(keys[i:i + step])
            est = h.count()
            dt = time.perf_counter() - t0
            err = abs(est - n) / n
            out[mode] = {"keys_per_sec": n / dt, "seconds": dt, "error": err}
            assert err < 0.02, f"error {err} out of envelope"
        finally:
            _close(c)
    return {"config": 1, "n_keys": n, **out,
            "speedup": out["engine"]["keys_per_sec"] / out["redis"]["keys_per_sec"]}


def config2(full: bool):
    """Bloom k=7 / m=2^28: 10M inserts + contains() + FPR measured with 1B
    fresh probe keys (the BASELINE "FPR @ 1B keys" metric: at ~3e-5
    theoretical FPR you need ~1e9 probes for 3 significant digits).

    Keys ride the uint64 fast path (hashed as 8-byte LE on device —
    bit-identical membership to the byte path on the same encodings)."""
    n = _scale(10_000_000 if full else 1_000_000)
    n_probe = _scale(1_000_000_000 if full else 2_000_000)
    m = 1 << 28
    c = _mkclient("engine")
    try:
        bf = c.get_bloom_filter("b2:bloom")
        # Reference sizing solves (n, p) -> (m, k); pick p to land on k=7/2^28.
        bf.try_init(expected_insertions=m // 10, false_probability=0.01)
        size = bf.get_size()
        k = bf.get_hash_iterations()
        rng = np.random.default_rng(7)
        step = 1 << 20
        # Warm the ingest path OUTSIDE the timer (config 1 policy): the
        # first bloom op pays the one-time link probe + path selection.
        warm = c.get_bloom_filter("b2:warm")
        warm.try_init(expected_insertions=100_000, false_probability=0.01)
        # private rng: consuming draws from `rng` would desync the
        # regenerated first-batch sample below
        wkeys = np.random.default_rng(99).integers(0, 2**63, 1 << 17, np.uint64)
        warm.add_ints(wkeys)
        warm.contains_ints(wkeys)
        # Inserted keys live in [0, 2^63); probes in [2^63, 2^64) — disjoint
        # by construction, so every probe hit is a genuine false positive.
        # Inserts ride 2M-key batches (halves the per-batch staging
        # overhead on top of the ~12M keys/s native fold floor); the first
        # `step` keys must still match the regenerated sample below, and
        # they do — the rng draw order is identical, only the slicing
        # into batches changes.
        ins_step = step * 2
        t0 = time.perf_counter()
        futs = []
        for s in range(0, n, ins_step):
            keys = rng.integers(0, 2**63, min(ins_step, n - s), np.uint64)
            futs.append(bf.add_ints_async(keys))
        for f in futs:
            f.result()
        insert_dt = time.perf_counter() - t0

        # First insert batch, regenerated from the same seed: must all hit.
        sample = np.random.default_rng(7).integers(
            0, 2**63, min(step, n), np.uint64)
        t0 = time.perf_counter()
        hits = bf.contains_ints(sample)
        contains_dt = time.perf_counter() - t0
        assert hits.all(), "false negatives!"

        # FPR probe. Probes live in [2^63, 2^64), inserts in [0, 2^63) —
        # disjoint by construction, so every probe hit is a genuine false
        # positive. At full scale, 1B probes = 7.6 GB of host key traffic,
        # which the host link cannot move in reasonable time; the probes
        # are synthetic, so full runs draw them on-accelerator and use the
        # contains_count reduce (a 4-byte scalar per batch comes back).
        import jax

        devgen = full and jax.default_backend() != "cpu"
        if devgen:
            import jax.numpy as jnp

            @jax.jit
            def gen_probe(gk):
                k1, k2, k3 = jax.random.split(gk, 3)
                lo = jax.random.bits(k1, (step,), jnp.uint32)
                # force the top bit so hi in [2^31, 2^32) -> key >= 2^63
                hi = jax.random.bits(k2, (step,), jnp.uint32) | jnp.uint32(
                    0x80000000)
                return jnp.stack([lo, hi], axis=1), k3

            genkey = jax.random.PRNGKey(72)
            # Compile gen + count kernels OUTSIDE the timed region (config4
            # pattern) so probe_dt measures probes, not XLA.
            warm, genkey = gen_probe(genkey)
            bf.contains_count_device_async(warm).result()

            def probe_batch(s):
                nonlocal genkey
                fresh, genkey = gen_probe(genkey)
                return bf.contains_count_device_async(fresh), step
        else:
            rng2 = np.random.default_rng(72)

            def probe_batch(s):
                fresh = rng2.integers(2**63, 2**64, min(step, n_probe - s),
                                      dtype=np.uint64)
                return bf.contains_ints_async(fresh), fresh.size

        def drain(pending):
            return sum(int(np.sum(p.result())) for p in pending)

        false_hits = 0
        probed = 0
        pending = []
        t0 = time.perf_counter()
        for s in range(0, n_probe, step):
            fut, batch_count = probe_batch(s)
            pending.append(fut)
            probed += batch_count
            if len(pending) >= 8:
                false_hits += drain(pending)
                pending = []
            if s and s % (100 * step) == 0:
                print(f"#   fpr probe {probed/1e6:.0f}M/{n_probe/1e6:.0f}M",
                      file=sys.stderr)
        false_hits += drain(pending)
        probe_dt = time.perf_counter() - t0
        fpr = false_hits / probed
        return {"config": 2, "n_keys": n, "m_bits": size, "k": k,
                "insert_keys_per_sec": n / insert_dt,
                "contains_keys_per_sec": sample.size / contains_dt,
                "fpr_probes": probed,
                "fpr_probe_source": "device" if devgen else "host",
                "fpr_probe_keys_per_sec": probed / probe_dt,
                "measured_fpr": fpr}
    finally:
        _close(c)


def config3(full: bool):
    """RBatch pipelined PFADD across 256 sketches + PFMERGE union."""
    sketches = 256
    per = _scale(40_000 if full else 4_000)
    c = _mkclient("engine")
    try:
        rng = np.random.default_rng(3)
        # Pre-generate key material OUTSIDE the timed region (10M python
        # tobytes() calls are synthetic-workload setup, not framework work).
        all_keys = [
            [k.tobytes() for k in rng.integers(0, 2**63, per, np.uint64)]
            for _ in range(sketches)
        ]
        # Warm the add path at the timed shape on a scratch sketch.
        c.get_hyper_log_log("b3:warmadd").add_all(all_keys[0])
        batch = c.create_batch()
        t0 = time.perf_counter()
        for s in range(sketches):
            batch.get_hyper_log_log(f"b3:s{s}").add_all_async(all_keys[s])
            # staging copied the keys into the encoded numpy batch; drop the
            # bytes objects so ~0.5 GB doesn't sit across execute/merge.
            all_keys[s] = None
        batch.execute()
        add_dt = time.perf_counter() - t0

        dest = c.get_hyper_log_log("b3:merged")
        names = [f"b3:s{s}" for s in range(sketches)]
        # Warm the merge/count kernels at this sketch-count shape so the
        # timed passes measure the operation, not its one-time XLA compile —
        # the fused kernel for the blocking shot AND the separate
        # merge/count pair the pipelined loop below uses.
        warm = c.get_hyper_log_log("b3:warm")
        warm.merge_with_and_count(*names)
        warm.merge_with(*names)
        warm.count()
        rtt_ms = _link_rtt_ms()
        # Blocking single shot via the FUSED merge+count op: exactly one
        # dependent D2H sync (one link round trip; read it against
        # rtt_ms). r4's
        # merge_with()+count() paid ~3 RTTs; the fused op is the reference's
        # one-round-trip PFMERGE+PFCOUNT batch shape
        # (RedissonHyperLogLog.java:78-97).
        t0 = time.perf_counter()
        union = dest.merge_with_and_count(*names)
        sync_dt = time.perf_counter() - t0
        # Steady state: K merge+count cycles THROUGH THE ASYNC FACADE
        # (merge_with_async/count_async are first-class reference API,
        # RedissonHyperLogLog.java:40-97) — per-op cost with the link RTT
        # amortized, i.e. what an attached chip sees per blocking call.
        K = 8
        futs = []
        t0 = time.perf_counter()
        for _ in range(K):
            futs.append(dest.merge_with_async(*names))
            futs.append(dest.count_async())
        for f in futs:
            f.result()  # merge futures included: a failed merge must raise
        pipe_dt = (time.perf_counter() - t0) / K
        # merge_count_ms keeps its historical meaning (blocking single
        # shot); the pipelined per-op figure is a separate, clearly-named
        # key so round-over-round diffs never compare different metrics.
        return {"config": 3, "sketches": sketches, "keys_per_sketch": per,
                "batched_insert_keys_per_sec": sketches * per / add_dt,
                "merge_count_ms": sync_dt * 1000,
                "merge_count_pipelined_ms": pipe_dt * 1000,
                "link_rtt_ms": rtt_ms,
                "union_estimate": union}
    finally:
        _close(c)


def _link_rtt_ms() -> float:
    """One dependent device sync of a trivial kernel = the link's
    round-trip floor (not framework cost — published alongside blocking
    latencies so they can be read against it)."""
    import jax
    import jax.numpy as jnp

    # graftlint: allow-recompile(the dispatch-floor probe measures exactly this one-time compile+dispatch)
    tick = jax.jit(lambda x: x + 1)
    float(tick(jnp.float32(0)))  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(tick(jnp.float32(0)))
        best = min(best, time.perf_counter() - t0)
    return best * 1000


def config4(full: bool):
    """Streaming cardinality: Zipf keys over 4K sharded HLLs + periodic merge.

    BASELINE size is 1B keys; default trims to 32M (same code path). Keys
    stream through the pod bank (row = key % 4096) with a merge-count every
    8 batches.
    """
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    total = _scale(1_000_000_000 if full else 32_000_000)
    batch_n = 1 << (14 if _TINY else 20)
    n_sketches = 512 if _TINY else 4096

    cfg = Config()
    pod = cfg.use_pod()
    pod.bank_capacity = n_sketches
    pod.ingest = _INGEST
    c = RedissonTPU.create(cfg)
    try:
        backend = c._backend.sketch
        from redisson_tpu.parallel import sharded

        import jax
        import jax.numpy as jnp

        # At BASELINE scale the stream must not be bounded by the host link
        # (1 B keys of host traffic is minutes of DMA alone). The keys are
        # *synthetic* by spec, so full
        # runs draw them on-accelerator: same Zipf-ish skew, same insert
        # path, zero host->device key traffic. CI-sized runs keep the
        # host-streamed path covered.
        devgen = full and jax.default_backend() != "cpu"

        rng = np.random.default_rng(4)
        seen_estimates = []
        nbatches = total // batch_n
        distinct_space = total // 10

        # Ground truth (VERDICT r4 next #6): the stream's exact distinct
        # count is tracked as a presence bitmap on whichever side GENERATES
        # the keys — one uint8 cell per possible key (space = total/10, so
        # 100 MB at the 1 B-key BASELINE scale), summed once at the end.
        # Both variants then publish a validated `error`, at the same scale.
        if devgen:
            presence = jnp.zeros((distinct_space + 1,), jnp.uint8)

            # graftlint: allow-recompile(compiled once per config run; the generator closure is per-run state)
            @functools.partial(jax.jit, donate_argnums=(1,))
            def gen_batch(key, presence):
                k1, k2 = jax.random.split(key)
                raw = jax.random.pareto(k1, 1.1, (batch_n,), jnp.float32)
                scaled = raw / jnp.max(raw) * distinct_space
                lo = scaled.astype(jnp.uint32)  # space < 2^32 by construction
                rows = (lo % n_sketches).astype(jnp.int32)
                presence = presence.at[lo].set(jnp.uint8(1))
                return lo, rows, k2, presence
            genkey = jax.random.PRNGKey(4)
            hi0 = jnp.zeros((batch_n,), jnp.uint32)
            valid0 = jnp.ones((batch_n,), bool)
            _, _, _, presence = gen_batch(genkey, presence)  # compile
            presence = jnp.zeros_like(presence)
        else:
            presence_h = np.zeros(distinct_space + 1, bool)

        t0 = time.perf_counter()
        for b in range(nbatches):
            if devgen:
                lo, rows, genkey, presence = gen_batch(genkey, presence)
                hi, valid = hi0, valid0
            else:
                # Zipf-ish skew: pareto draw bounded to the distinct space
                raw = rng.pareto(1.1, batch_n)
                keys = (raw / raw.max() * distinct_space).astype(np.uint64)
                presence_h[keys] = True
                hi = (keys >> np.uint64(32)).astype(np.uint32)
                lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
                rows = (keys % np.uint64(n_sketches)).astype(np.int32)
                valid = np.ones(batch_n, bool)
            backend.bank, _ = sharded.bank_insert(
                backend.bank, hi, lo, rows, valid, backend.mesh, backend.seed)
            if b % 8 == 7:
                seen_estimates.append(
                    float(sharded.bank_count_all(backend.bank, backend.mesh)))
            if b and b % 100 == 0:
                print(f"#   streamed {b * batch_n / 1e6:.0f}M/"
                      f"{total / 1e6:.0f}M keys", file=sys.stderr)
        backend.bank.block_until_ready()
        dt = time.perf_counter() - t0
        # True FINAL union (the last periodic merge predates the tail
        # batches — validating against ground truth needs the real end
        # state, not a mid-stream snapshot).
        est = float(sharded.bank_count_all(backend.bank, backend.mesh))
        seen_estimates.append(est)
        # graftlint: allow-int-reduce(presence is one cell per distinct key; distinct_space << 2^31)
        exact = int(jnp.sum(presence.astype(jnp.int32))) if devgen \
            else int(presence_h.sum())
        out = {"config": 4, "total_keys": nbatches * batch_n,
               "sharded_hlls": n_sketches,
               "keys_per_sec": nbatches * batch_n / dt,
               "key_source": "device" if devgen else "host",
               "final_estimate": est,
               "true_distinct": exact,
               "error": (abs(est - exact) / exact
                         if est is not None and exact else None),
               "periodic_merges": len(seen_estimates)}
        # VERDICT r3 weak #4: the published row must ALSO exercise the real
        # host-ingest machinery (host generates + natively folds the
        # stream; device absorbs bank uploads), not only generated keys.
        # Same scale as the device variant (VERDICT r4 next #6), and a
        # FRESH bank — its estimate must not include the device variant's
        # keys or the two numbers can't be compared.
        out["host_ingest"] = _config4_host_ingest(
            backend, batch_n, n_sketches, total)
        return out
    finally:
        c.shutdown()


def _config4_host_ingest(backend, batch_n: int, n_sketches: int, total: int):
    """Sustained host-side streaming into the sharded bank: the host
    generates AND folds the Zipf stream natively (hll_fold_u64_rows into a
    [S, 16384] bank mirror); the device absorbs one bank upload per
    interval — the transfer-adaptive move (ship the reduction, not
    8 B/key). Returns the measured rate plus the bottleneck budget."""
    from redisson_tpu import native as native_mod
    from redisson_tpu.parallel import sharded

    if not native_mod.available():
        return {"skipped": "native library unavailable"}
    rng = np.random.default_rng(44)
    host_bank = np.zeros((n_sketches, 16384), np.uint8)
    # Self-contained state: absorbing into the caller's bank would mix the
    # device variant's keys into this estimate (VERDICT r4 next #6 — the
    # two variants' 6% disagreement was uninterpretable).
    dev_bank = sharded.make_bank(backend.mesh, n_sketches)
    nbatches = max(total // batch_n, 1)
    absorb_every = max(nbatches // 8, 1)
    distinct_space = total // 10
    presence = np.zeros(distinct_space + 1, bool)  # exact ground truth
    fold_s = gen_s = absorb_s = 0.0
    absorbs = 0
    t0 = time.perf_counter()
    for b in range(nbatches):
        tg = time.perf_counter()
        raw = rng.pareto(1.1, batch_n)
        keys = (raw / raw.max() * distinct_space).astype(np.uint64)
        presence[keys] = True
        rows = (keys % np.uint64(n_sketches)).astype(np.int32)
        gen_s += time.perf_counter() - tg
        tf = time.perf_counter()
        native_mod.hll_fold_u64_rows(keys, rows, host_bank, backend.seed)
        fold_s += time.perf_counter() - tf
        if b % absorb_every == absorb_every - 1:
            ta = time.perf_counter()
            dev_bank = sharded.bank_absorb_host(
                dev_bank, host_bank, backend.mesh)
            dev_bank.block_until_ready()
            absorb_s += time.perf_counter() - ta
            absorbs += 1
        if b and b % 100 == 0:
            print(f"#   host-ingest {b * batch_n / 1e6:.0f}M/"
                  f"{total / 1e6:.0f}M keys", file=sys.stderr)
    if nbatches % absorb_every:  # tail batches folded since the last absorb
        ta = time.perf_counter()
        dev_bank = sharded.bank_absorb_host(dev_bank, host_bank, backend.mesh)
        dev_bank.block_until_ready()
        absorb_s += time.perf_counter() - ta
        absorbs += 1
    dt = time.perf_counter() - t0
    est = float(sharded.bank_count_all(dev_bank, backend.mesh))
    exact = int(presence.sum())
    return {"total_keys": nbatches * batch_n,
            "keys_per_sec": nbatches * batch_n / dt,
            "key_source": "host",
            "final_estimate": est,
            "true_distinct": exact,
            "error": abs(est - exact) / exact if exact else None,
            "budget": {"keygen_s": gen_s, "native_fold_s": fold_s,
                       "absorb_transfer_s": absorb_s, "absorbs": absorbs,
                       "bank_mb_per_absorb":
                           host_bank.nbytes / 1e6}}


def config5(full: bool):
    """Cluster-mode count-distinct THROUGH THE CLIENT FACADE: 1024 named
    HLLs live as mesh-sharded bank rows; inserts are staged per sketch via
    RBatch (the pod backend's GLOBAL_COALESCE folds them into shared SPMD
    calls with per-key target rows), and the cross-slot merge is
    `get_hyper_log_log(...).count_with(*names)` — one gather + row-max +
    pmax-allreduce kernel. No `c._backend.sketch` reaching (VERDICT r3:
    the reference's mergeWith/countWith are first-class API,
    RedissonHyperLogLog.java:40-97, so the <50 ms target must hold here)."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    n_sketches = 64 if _TINY else 1024
    per = _scale(100_000 if full else 20_000)

    cfg = Config()
    pod = cfg.use_pod()
    pod.bank_capacity = n_sketches
    pod.ingest = _INGEST
    c = RedissonTPU.create(cfg)
    try:
        rng = np.random.default_rng(5)
        keys = rng.integers(0, 2**63, n_sketches * per, np.uint64)
        names = [f"b5:s{i}" for i in range(n_sketches)]
        batch = c.create_batch()
        for i, name in enumerate(names):
            batch.get_hyper_log_log(name).add_ints_async(
                keys[i * per:(i + 1) * per])
        t0 = time.perf_counter()
        batch.execute()
        insert_dt = time.perf_counter() - t0

        # Compile outside the timed region; blocking best-of-3 plus the
        # pipelined steady state (same split as config 3: one link round
        # trip rides on every blocking call).
        h0 = c.get_hyper_log_log(names[0])
        h0.count_with(*names[1:])
        rtt_ms = _link_rtt_ms()
        sync_dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            est = h0.count_with(*names[1:])
            sync_dt = min(sync_dt, time.perf_counter() - t0)
        K = 8
        t0 = time.perf_counter()
        futs = [h0.count_with_async(*names[1:]) for _ in range(K)]
        for f in futs:
            f.result()
        pipe_dt = (time.perf_counter() - t0) / K
        err = abs(est - keys.size) / keys.size
        backend = c._backend.sketch
        # Same key discipline as config 3: the historical key stays the
        # blocking measurement; pipelined gets its own name.
        return {"config": 5, "sketches": n_sketches,
                "cross_slot_merge_count_ms": sync_dt * 1000,
                "cross_slot_merge_count_pipelined_ms": pipe_dt * 1000,
                "link_rtt_ms": rtt_ms,
                "insert_keys_per_sec": keys.size / insert_dt,
                "union_estimate": est, "true_distinct": int(keys.size),
                "error": err, "devices": int(backend.mesh.devices.size),
                "api": "facade"}
    finally:
        c.shutdown()


CONFIGS = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5}


def serve_smoke():
    """Offered-load sweep through the QoS serving layer over a simulated
    fixed-rate backend (no device): at each multiple of capacity, submit
    paced ops for ~a second and report the shed rate plus p50/p99 *queueing*
    delay (enqueue -> dispatch, measured at the backend off `op.enqueued_at`).
    The expected shape: sheds appear only above 1x while admitted-op
    queueing delay stays bounded by the configured budget — that bound is
    what admission control buys."""
    import threading

    from redisson_tpu.config import ServeConfig
    from redisson_tpu.executor import CommandExecutor
    from redisson_tpu.observability import ExecutorMetrics, MetricsRegistry
    from redisson_tpu.serve import (AdaptiveBatchPolicy, CostModel,
                                    RejectedError, ServingLayer)

    cap_keys = 2_000_000  # simulated backend service rate, keys/s
    op_keys = 1000
    budget_s = 0.05

    class SimBackend:
        """Serves keys at a fixed rate; records per-op queueing delay."""

        def __init__(self):
            self.delays = []

        def run(self, kind, target, ops):
            now = time.monotonic()
            self.delays.extend(now - op.enqueued_at for op in ops)
            time.sleep(sum(max(1, op.nkeys) for op in ops) / cap_keys)
            for op in ops:
                op.future.set_result(op.nkeys)

    print(f"# serve-smoke: simulated backend {cap_keys/1e6:.1f}M keys/s, "
          f"{op_keys}-key ops, queue-delay budget {budget_s*1e3:.0f}ms",
          file=sys.stderr)
    print(f"{'load':>6} {'submitted':>9} {'shed%':>7} "
          f"{'qd_p50_ms':>9} {'qd_p99_ms':>9}")
    ok = True
    for mult in (0.5, 1.0, 2.0, 4.0):
        registry = MetricsRegistry()
        cfg = ServeConfig(max_queue_ops=64, max_queue_delay_s=budget_s,
                          default_timeout_ms=0, retry_attempts=0,
                          max_linger_s=0.0005, min_batch_keys=op_keys)
        backend = SimBackend()
        policy = AdaptiveBatchPolicy(
            CostModel(), max_linger_s=cfg.max_linger_s,
            target_batch_service_s=cfg.target_batch_service_s,
            min_batch_keys=cfg.min_batch_keys)
        ex = CommandExecutor(backend, metrics=ExecutorMetrics(registry),
                             policy=policy)
        serve = ServingLayer(ex, cfg, registry=registry)
        shed = [0]
        other = [0]
        lock = threading.Lock()

        def on_done(f):
            exc = f.exception()
            if isinstance(exc, RejectedError):
                with lock:
                    shed[0] += 1
            elif exc is not None:
                with lock:
                    other[0] += 1

        offered_ops = cap_keys * mult / op_keys
        interval = 1.0 / offered_ops
        nsub = 0
        next_t = time.monotonic()
        t_end = next_t + 1.0
        while time.monotonic() < t_end:
            now = time.monotonic()
            if now < next_t:
                time.sleep(min(next_t - now, 0.002))
                continue
            next_t += interval
            serve.execute_async("smoke", "hll_add", None,
                                nkeys=op_keys).add_done_callback(on_done)
            nsub += 1
        serve.shutdown(timeout=10.0)
        delays = np.array(backend.delays) if backend.delays else np.zeros(1)
        p50, p99 = np.percentile(delays, [50, 99])
        shed_pct = 100.0 * shed[0] / max(1, nsub)
        print(f"{mult:>5.1f}x {nsub:>9} {shed_pct:>6.1f}% "
              f"{p50*1e3:>9.2f} {p99*1e3:>9.2f}")
        if other[0]:
            print(f"#   {other[0]} op(s) failed with non-shed errors",
                  file=sys.stderr)
            ok = False
        if p99 > 4 * budget_s:  # generous CI slack over the 50ms budget
            print(f"#   p99 queueing delay {p99*1e3:.1f}ms blew the budget",
                  file=sys.stderr)
            ok = False
    return ok


def pipeline_smoke():
    """In-flight window sweep over a device-latency sim backend: verifies
    results are bit-identical at every depth, reports wall time + overlap
    ratio, then measures the epoch read cache's hit rate through a real
    local-mode client. Exit contract (the CPU-only CI acceptance for PR 4):
    overlap ratio > 0 at window >= 2 AND identical results to window 1."""
    import queue as queue_mod
    import threading

    from redisson_tpu.executor import CommandExecutor

    device_s = 0.004
    host_s = 0.002  # pad + device_put staging cost, paid on the dispatcher
    n_ops = 120
    n_targets = 8

    class SimBackend:
        """Commits state at stage time (dispatch-time state, like the TPU
        tier), resolves futures on a worker after simulated device time.
        run() charges a host staging cost on the dispatcher thread — the
        component the pipeline hides behind device compute."""

        DISPATCH_TIME_STATE = True

        def __init__(self):
            self.state = {}
            self._q = queue_mod.Queue()
            self._t = threading.Thread(target=self._drain, daemon=True)
            self._t.start()

        def run(self, kind, target, ops):
            time.sleep(host_s)  # simulated pad + H2D transfer
            staged = []
            for op in ops:
                vals = self.state.setdefault(op.target, [])
                if op.kind == "set":
                    vals.append(op.payload)
                    staged.append((op, len(vals)))
                else:
                    staged.append((op, list(vals)))
            self._q.put(staged)

        def _drain(self):
            while True:
                item = self._q.get()
                if item is None:
                    return
                time.sleep(device_s)  # simulated device compute + D2H
                for op, val in item:
                    if not op.future.done():
                        op.future.set_result(val)

        def close(self):
            self._q.put(None)
            self._t.join(timeout=5)

    rng = np.random.default_rng(11)
    schedule = [(f"t{int(rng.integers(n_targets))}",
                 "set" if rng.random() < 0.7 else "get",
                 int(rng.integers(1000)))
                for _ in range(n_ops)]

    def play(window):
        backend = SimBackend()
        ex = CommandExecutor(backend, inflight_runs=window)
        t0 = time.perf_counter()
        futs = [ex.execute_async(t, k, p, nkeys=1) for t, k, p in schedule]
        results = [f.result(timeout=60) for f in futs]
        dt = time.perf_counter() - t0
        stats = ex.pipeline_stats()
        ex.shutdown()
        backend.close()
        return results, dt, stats

    print(f"# pipeline-smoke: {n_ops} ops over {n_targets} targets, "
          f"{device_s * 1e3:.0f}ms simulated device time per run",
          file=sys.stderr)
    print(f"{'window':>6} {'wall_s':>8} {'overlap%':>9} {'runs':>6} "
          f"{'identical':>9}")
    base_results = None
    ok = True
    for window in (1, 2, 4):
        results, dt, stats = play(window)
        identical = base_results is None or results == base_results
        if base_results is None:
            base_results = results
        print(f"{window:>6} {dt:>8.3f} {100 * stats['overlap_ratio']:>8.1f}% "
              f"{stats['runs_completed']:>6} {str(identical):>9}")
        if not identical:
            print(f"#   window={window} results diverged from serial",
                  file=sys.stderr)
            ok = False
        if window >= 2 and stats["overlap_ratio"] <= 0.0:
            print(f"#   window={window}: no overlap observed", file=sys.stderr)
            ok = False

    # Epoch read cache through the real client (local-mode sketch engine).
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    c = RedissonTPU.create(Config())
    try:
        h = c.get_hyper_log_log("psmoke:hll")
        h.add_all(list(range(10_000)))
        reads = 20
        t0 = time.perf_counter()
        for _ in range(reads):
            h.count()
        read_dt = time.perf_counter() - t0
        stats = c._routing.sketch.read_cache.stats()
        print(f"# read-cache: {reads} counts in {read_dt * 1e3:.1f}ms, "
              f"hit ratio {stats['hit_ratio']:.2f} "
              f"({stats['hits']} hits / {stats['misses']} misses)")
        if stats["hits"] < reads - 2:
            print("#   read cache barely hit", file=sys.stderr)
            ok = False
    finally:
        c.shutdown()
    return ok


def delta_smoke():
    """Delta-ingest acceptance smoke (the CPU-only CI contract for the
    delta tentpole):

      1. a mixed hll/bloom/bitset workload run once with ingest="delta"
         and once with ingest="device" (scatter) must land in
         BIT-IDENTICAL device state with identical per-op results;
      2. a 1M-key PFADD batch must ship < 1/8 of the raw-key bytes over
         the link (the dense 16 KB register plane vs 8 B/key);
      3. with the in-flight window >= 2, host folds must overlap device
         merges (executor overlap ratio > 0).
    """
    from redisson_tpu import native as native_mod

    if not native_mod.available():
        print("# delta-smoke: native library unavailable; SKIP",
              file=sys.stderr)
        return True
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config, TpuConfig

    # The <1/8 link criterion needs n > 16384 (dense HLL plane is 16 KB
    # vs 8 B/key raw), so the tiny scale floors at 128K keys, not _scale.
    n = 1 << (17 if _TINY else 20)
    rng = np.random.default_rng(21)
    hll_batches = [rng.integers(0, 2**63, n, np.uint64) for _ in range(4)]
    bloom_batches = [rng.integers(0, 2**63, 1 << 13, np.uint64)
                     for _ in range(3)]
    bloom_batches.append(bloom_batches[0])  # re-adds: try_add must say False
    bits_batches = [rng.integers(0, 1 << 16, 1 << 12, np.int64)
                    for _ in range(3)]
    bits_batches.append(bits_batches[0])  # re-sets: old bits must say True

    def play(ingest):
        c = RedissonTPU.create(Config(tpu=TpuConfig(ingest=ingest)))
        try:
            results = []
            hs = [c.get_hyper_log_log(f"ds:h{i}") for i in range(2)]
            bf = c.get_bloom_filter("ds:bloom")
            bf.try_init(expected_insertions=200_000, false_probability=0.01)
            bs = c.get_bit_set("ds:bits")
            # Serial op-by-op: both paths must agree per op, and serial
            # submission pins the visibility point (each op sees all
            # earlier ops' state) so the comparison is exact.
            for i, b in enumerate(hll_batches):
                results.append(bool(hs[i % 2].add_ints(b)))
            for b in bloom_batches:
                results.append(bf.add_ints(b).tolist())
            for b in bits_batches:
                results.append(bs.set_bits(b).tolist())
            be = c._routing.sketch
            state = {}
            bank = np.asarray(be._ensure_bank())
            for i in range(2):
                state[f"ds:h{i}"] = bank[be._rows[f"ds:h{i}"]].copy()
            be._bloom_device_sync("ds:bloom")  # host-mirror path parity
            for name in ("ds:bloom", "ds:bits"):
                state[name] = np.asarray(be.store.get(name).state).copy()
            return results, state
        finally:
            _close(c)

    ok = True
    res_d, state_d = play("delta")
    res_s, state_s = play("device")
    identical = res_d == res_s and all(
        np.array_equal(state_d[k], state_s[k]) for k in state_s)
    print(f"# delta-smoke: delta vs scatter — results "
          f"{'identical' if res_d == res_s else 'DIVERGED'}, state "
          f"{'bit-identical' if identical else 'MISMATCH'}")
    if not identical:
        for k in state_s:
            if not np.array_equal(state_d[k], state_s[k]):
                print(f"#   state mismatch: {k}", file=sys.stderr)
        ok = False

    # -- link bytes/key at the 1M-key batch ---------------------------------
    c = RedissonTPU.create(Config(tpu=TpuConfig(ingest="delta")))
    try:
        h = c.get_hyper_log_log("ds:link")
        h.add_ints(hll_batches[0])
        stats = c._routing.sketch.ingest_stats()
        ratio = stats["link_bytes"] / max(stats["raw_bytes"], 1)
        print(f"# delta-smoke: {stats['delta_bytes_per_key']:.4f} B/key "
              f"shipped vs 8 raw ({ratio:.4f} of raw; "
              f"{stats['merge_launches']} launch/"
              f"{stats['delta_runs']} run)")
        if ratio >= 1 / 8:
            print(f"#   link ratio {ratio:.3f} >= 1/8", file=sys.stderr)
            ok = False
    finally:
        _close(c)

    # -- fold/merge overlap with window >= 2 --------------------------------
    cfg = Config(tpu=TpuConfig(ingest="delta"))
    cfg.tpu.inflight_runs = 2
    # One op per run: cap the batch at one submission so the greedy policy
    # cannot collapse the burst into a single window (which would leave
    # nothing to overlap).
    cfg.tpu.max_batch_keys = n
    c = RedissonTPU.create(cfg)
    try:
        h = c.get_hyper_log_log("ds:ov")
        h.add_ints(hll_batches[0])  # warm compile outside the burst
        futs = [h.add_ints_async(hll_batches[i % len(hll_batches)])
                for i in range(8)]
        for f in futs:
            f.result(timeout=120)
        stats = c._executor.pipeline_stats()
        print(f"# delta-smoke: window=2 overlap ratio "
              f"{stats['overlap_ratio']:.2f} "
              f"({stats['runs_completed']} runs)")
        if stats["overlap_ratio"] <= 0.0:
            print("#   no fold/merge overlap observed", file=sys.stderr)
            ok = False
    finally:
        _close(c)
    return ok


def tape_smoke():
    """Window-megakernel acceptance smoke (the CPU-only CI contract for
    the tape tentpole):

      1. a mixed hll/bloom/bitset window run with ingest="tape" must
         retire in EXACTLY one fused launch per window
         (launches_per_window == 1.0, every window a tape run);
      2. the full workload's engine digest and per-op results must be
         bit-identical to ingest="scatter" (serial device scatter);
      3. --pipeline-smoke's serial-identity contract must still hold —
         re-run it here so the tape PR cannot green while regressing the
         pipeline (the tape window handoff threads through the same
         executor seam).
    """
    from redisson_tpu import native as native_mod

    if not native_mod.available():
        print("# tape-smoke: native library unavailable; SKIP",
              file=sys.stderr)
        return True
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config, TpuConfig

    n = 1 << (13 if _TINY else 16)
    rng = np.random.default_rng(23)
    hll_batches = [rng.integers(0, 2**63, n, np.uint64) for _ in range(3)]
    bloom_batches = [rng.integers(0, 2**63, 1 << 12, np.uint64)
                     for _ in range(2)]
    bloom_batches.append(bloom_batches[0])  # re-adds: try_add must say False
    bits_batches = [rng.integers(0, 1 << 16, 1 << 11, np.int64)
                    for _ in range(2)]
    bits_batches.append(bits_batches[0])  # re-sets: old bits must say True

    def play(ingest):
        c = RedissonTPU.create(Config(tpu=TpuConfig(ingest=ingest)))
        try:
            results = []
            hs = [c.get_hyper_log_log(f"ts:h{i}") for i in range(2)]
            bf = c.get_bloom_filter("ts:bloom")
            bf.try_init(expected_insertions=100_000, false_probability=0.01)
            bs = c.get_bit_set("ts:bits")
            # Mixed async bursts: each burst stacks all three kinds into
            # one pipeline window (the tape arena), then serial re-adds
            # pin the per-op result contract exactly.
            for i in range(3):
                futs = [
                    hs[i % 2].add_ints_async(hll_batches[i]),
                    bf.add_ints_async(bloom_batches[i]),
                    bs.set_bits_async(bits_batches[i]),
                ]
                results.extend(np.asarray(f.result(timeout=120)).tolist()
                               for f in futs)
            be = c._routing.sketch
            be._bloom_device_sync("ts:bloom")  # host-mirror path parity
            stats = be.ingest_stats()
            digest = _engine_digest(c)
            return results, digest, stats
        finally:
            _close(c)

    ok = True
    res_t, dig_t, stats_t = play("tape")
    res_s, dig_s, _ = play("scatter")

    windows = stats_t["delta_runs"] + stats_t["tape_runs"]
    lpw = stats_t["launches_per_window"]
    print(f"# tape-smoke: {stats_t['tape_runs']} tape runs / "
          f"{windows} windows, {lpw:.2f} launches/window "
          f"({stats_t['launch_us_per_window']:.0f} us/window)")
    if stats_t["tape_runs"] < 1 or stats_t["delta_runs"] != 0:
        print("#   not every window retired through the tape",
              file=sys.stderr)
        ok = False
    if lpw != 1.0:
        print(f"#   launches_per_window {lpw} != 1.0", file=sys.stderr)
        ok = False

    identical = res_t == res_s and dig_t == dig_s
    print(f"# tape-smoke: tape vs scatter — results "
          f"{'identical' if res_t == res_s else 'DIVERGED'}, digest "
          f"{'bit-identical' if dig_t == dig_s else 'MISMATCH'}")
    if not identical:
        ok = False

    print("# tape-smoke: re-running pipeline smoke under the tape PR")
    if not pipeline_smoke():
        print("#   pipeline smoke regressed", file=sys.stderr)
        ok = False
    return ok


def _canon_state(obj, h):
    """Canonical identity-free rendering (raw pickle bytes differ across
    equal graphs when internal sharing differs — pickle memoizes by id)."""
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        h.update(repr(obj).encode())
    elif isinstance(obj, (bytearray, memoryview)):
        h.update(b"B" + bytes(obj))
    elif isinstance(obj, dict):
        h.update(b"{")
        for k, v in obj.items():
            _canon_state(k, h)
            h.update(b":")
            _canon_state(v, h)
        h.update(b"}")
    elif isinstance(obj, (list, tuple)):
        h.update(b"[")
        for v in obj:
            _canon_state(v, h)
            h.update(b",")
        h.update(b"]")
    elif isinstance(obj, (set, frozenset)):
        h.update(b"<")
        for r in sorted(repr(v) for v in obj):
            h.update(r.encode() + b",")
        h.update(b">")
    elif isinstance(obj, np.ndarray):
        h.update(str(obj.dtype).encode() + str(obj.shape).encode())
        h.update(obj.tobytes())
    else:
        h.update(type(obj).__name__.encode())
        state = getattr(obj, "__dict__", None)
        _canon_state(state if state is not None else repr(obj), h)


def _engine_digest(client) -> str:
    """Bit-identical engine fingerprint (sketch arrays + structure tier) —
    the same definition tests/test_persist.py pins recovery against."""
    import hashlib
    import pickle

    h = hashlib.sha256()
    store = client._store
    for name in sorted(store.keys()):
        obj = store.get(name)
        if obj is None:
            continue
        arr = np.asarray(obj.state)
        h.update(name.encode())
        h.update(str(obj.otype).encode())
        h.update(str(arr.dtype).encode() + str(arr.shape).encode())
        h.update(arr.tobytes())
        h.update(repr(sorted(obj.meta.items())).encode())
    structures = getattr(client._routing, "structures", None)
    if structures is not None:
        _canon_state(pickle.loads(structures.dump_state()), h)
    return h.hexdigest()


def persist_smoke():
    """fsync-policy sweep through the write-ahead journal on the real local
    client: a pipelined batched-insert workload (async submits, window =
    Config.inflight_runs >= 2) per policy {none, off, everysec, always},
    reporting wall time, overhead vs the journal-less baseline, and journal
    stats. Then every persisted directory is treated as a crash image and
    recovered into a fresh engine, which must be digest-identical to its
    leader. Exit contract (the CPU-only CI acceptance for this PR):
    everysec overhead < 10% AND every recovery bit-identical."""
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    rounds = 60 if _TINY else 300
    batch = 64
    rng = np.random.default_rng(7)
    hll_batches = rng.integers(0, 2**63, size=(rounds, batch), dtype=np.uint64)

    def run_workload(c):
        """Batched inserts, submitted async so the dispatch window (>= 2)
        can overlap journal appends with device work."""
        pend = []
        h = c.get_hyper_log_log("ps:hll")
        m = c.get_map("ps:m")
        t0 = time.perf_counter()
        for i in range(rounds):
            pend.append(h.add_ints_async(hll_batches[i]))
            pend.append(m.put_async(f"f{i}", i))
            pend.append(c.get_bucket(f"ps:b{i % 32}").set_async(i))
            if len(pend) >= 4 * 3:
                for f in pend:
                    f.result(timeout=60)
                pend.clear()
        for f in pend:
            f.result(timeout=60)
        return time.perf_counter() - t0

    policies = ("none", "off", "everysec", "always")
    root = tempfile.mkdtemp(prefix="rtpu-persist-smoke-")
    walls, digests, jstats = {}, {}, {}
    ok = True
    try:
        for policy in policies:
            cfg = Config()
            cfg.use_local()
            if policy != "none":
                cfg.use_persist(os.path.join(root, policy)).fsync = policy
            c = RedissonTPU.create(cfg)
            try:
                run_workload(c)  # warm compile/caches
                c.flushall()
                # Best-of-N: walls are ~0.1s at tiny scale, where scheduler
                # jitter swamps the real journal cost. Every repeat issues
                # the identical op stream, so min is the honest estimate.
                repeats = 3 if _TINY else 2
                walls[policy] = min(run_workload(c) for _ in range(repeats))
                if policy != "none":
                    c.persist.journal.sync()
                    jstats[policy] = c.persist.journal.stats()
                    digests[policy] = _engine_digest(c)
                    # crash image: copy while the journal is quiescent
                    shutil.copytree(os.path.join(root, policy),
                                    os.path.join(root, policy + ".img"))
            finally:
                c.shutdown()

        base = walls["none"]
        print(f"{'fsync':>9} {'wall_s':>8} {'overhead%':>9} "
              f"{'fsyncs':>7} {'group_mean':>10}")
        for policy in policies:
            over = 100.0 * (walls[policy] / base - 1.0)
            st = jstats.get(policy, {})
            print(f"{policy:>9} {walls[policy]:>8.3f} {over:>8.1f}% "
                  f"{st.get('fsyncs', 0):>7} {st.get('group_mean', 0.0):>10.2f}")
            if policy == "everysec" and over >= 10.0:
                print(f"#   everysec overhead {over:.1f}% >= 10% budget",
                      file=sys.stderr)
                ok = False

        for policy in ("off", "everysec", "always"):
            r = RedissonTPU.create(_persist_cfg(os.path.join(root, policy + ".img")))
            try:
                rec = r.persist.last_recovery or {}
                same = _engine_digest(r) == digests[policy]
                print(f"# recover[{policy}]: replayed {rec.get('replayed', 0)} "
                      f"ops at {rec.get('ops_per_s', 0.0):.0f} op/s, "
                      f"digest {'identical' if same else 'MISMATCH'}")
                if not same or rec.get("replay_errors"):
                    ok = False
            finally:
                r.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return ok


def _persist_cfg(path):
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_local()
    cfg.use_persist(path)
    return cfg


def chaos_smoke():
    """Seeded chaos through the fault subsystem (PR 8) on the real local
    client. Three gates, all on the CPU-only CI path:

      (a) RECOVERY/RETRY: pre-commit retryable plans (stage_h2d /
          kernel_launch / journal_fsync) — every op must ack (the serve
          retry absorbs the faults) and the engine digest must be
          bit-identical to a fault-free oracle;
      (b) REBUILD: a state-uncertain d2h plan — every future completes
          (typed fault or success, never a hang), the HBM rebuild
          settles with no failures, and the surviving state must
          digest-equal a fresh recovery of the committed journal (no
          acked write lost, no stranded future);
      (c) OVERHEAD: with the subsystem wired but idle (no plan), the
          same workload must cost < 1% over a bare client — the
          disabled `fire()` seam is one module-global read.
    """
    import random
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    rounds = 60 if _TINY else 240
    rng = np.random.default_rng(13)
    hll_batches = rng.integers(0, 2**63, size=(rounds, 32), dtype=np.uint64)

    def make_cfg(persist_dir=None, plan=None, faults=False):
        cfg = Config()
        cfg.use_local()
        sc = cfg.use_serve()
        sc.retry_interval_ms = 5
        if persist_dir is not None:
            cfg.use_persist(persist_dir).fsync = "always"
        if faults or plan:
            fc = cfg.use_faults()
            fc.plan = plan or []
        return cfg

    def run_workload(c, chaos=False):
        """hll/bitset/bloom mix. chaos=False asserts every op acks and
        returns the wall; chaos=True collects outcome names instead."""
        h = c.get_hyper_log_log("cs:hll")
        bits = c.get_bit_set("cs:bits")
        bloom = c.get_bloom_filter("cs:bloom")
        bloom.try_init(4096, 0.01)
        outcomes = []
        t0 = time.perf_counter()
        for i in range(rounds):
            try:
                h.add_ints(hll_batches[i])
                bits.set(i % 997, True)
                bloom.add(f"b{i}")
                outcomes.append("ok")
            except Exception as exc:  # noqa: BLE001 - chaos audit
                if not chaos:
                    raise
                outcomes.append(type(exc).__name__)
        wall = time.perf_counter() - t0
        return wall, outcomes

    ok = True
    root = tempfile.mkdtemp(prefix="rtpu-chaos-smoke-")
    try:
        # -- (a) retry absorption: digest-identical to the oracle --------
        oracle = RedissonTPU.create(make_cfg())
        try:
            run_workload(oracle)
            want = _engine_digest(oracle)
        finally:
            oracle.shutdown()
        plan_rng = random.Random(0xC405)
        plan = [{"seam": plan_rng.choice(
                    ("stage_h2d", "kernel_launch", "journal_fsync")),
                 "fault": "retryable",
                 "nth": plan_rng.randint(1, rounds),
                 "times": plan_rng.randint(1, 2)} for _ in range(4)]
        c = RedissonTPU.create(make_cfg(os.path.join(root, "retry"), plan))
        try:
            _, outcomes = run_workload(c, chaos=True)
            acked = outcomes.count("ok")
            injected = c.fault.injector.injected
            retries = int(c.metrics.counter("serve.retries_total"))
            same = _engine_digest(c) == want
            print(f"# chaos-smoke[retry]: {acked}/{rounds} acked, "
                  f"{injected} injected, {retries} retries, digest "
                  f"{'identical' if same else 'MISMATCH'}")
            if acked != rounds or not same:
                ok = False
        finally:
            c.shutdown()

        # -- (b) uncertain fault -> quarantine -> rebuild -> recovery ----
        live_dir = os.path.join(root, "rebuild")
        plan = [{"seam": "d2h_complete", "fault": "state_uncertain",
                 "nth": rounds // 3},
                {"seam": "d2h_complete", "fault": "device_lost",
                 "nth": rounds // 2}]
        c = RedissonTPU.create(make_cfg(live_dir, plan))
        try:
            _, outcomes = run_workload(c, chaos=True)
            settled = c.fault.rebuild.wait_idle(timeout=60)
            snap = c.fault.rebuild.snapshot()
            c.persist.journal.sync()
            live = _engine_digest(c)
            print(f"# chaos-smoke[rebuild]: {outcomes.count('ok')}/{rounds} "
                  f"acked, rebuilt {snap['rebuilt_total']} targets "
                  f"({snap['replayed_total']} replayed, "
                  f"{snap['last_rebuild_s'] * 1e3:.1f} ms), "
                  f"failures={snap['rebuild_failures']}")
            if not settled or snap["rebuild_failures"] or snap["degraded"]:
                ok = False
        finally:
            c.shutdown()
        r = RedissonTPU.create(_persist_cfg(live_dir))
        try:
            same = _engine_digest(r) == live
            print(f"# chaos-smoke[rebuild]: recovered digest "
                  f"{'identical' if same else 'MISMATCH'} to live survivor")
            if not same:
                ok = False
        finally:
            r.shutdown()

        # -- (c) fault-free overhead ------------------------------------
        def best_wall(cfg):
            c = RedissonTPU.create(cfg)
            try:
                run_workload(c)  # warm compile/caches
                c.flushall()
                best = float("inf")
                for _ in range(3 if _TINY else 2):
                    best = min(best, run_workload(c)[0])
                    c.flushall()
                return best
            finally:
                c.shutdown()

        bare = best_wall(make_cfg())
        wired = best_wall(make_cfg(faults=True))
        over = 100.0 * (wired / bare - 1.0)
        print(f"# chaos-smoke[overhead]: {bare * 1e3:.1f} ms bare -> "
              f"{wired * 1e3:.1f} ms wired-idle ({over:+.2f}%)")
        if over >= 1.0:
            print(f"#   fault-free overhead {over:.2f}% >= 1% budget",
                  file=sys.stderr)
            ok = False
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return ok


def trace_smoke():
    """Trace-subsystem acceptance smoke (the CPU-only CI contract for the
    trace tentpole). Two gates:

      (a) OVERHEAD: the ingest workload with tracing wired at the default
          sampling stride (1/128) must cost < 1% wall over a bare client
          — maybe_begin is one counter increment + modulo per op;
      (b) ATTRIBUTION: with a fault-injected journal_fsync stall
          (fault/inject's "stall" rule — a slow fsync, not a failed one),
          the slowest SLOWLOG entry must attribute the majority of its
          latency to the journal stage.
    """
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    # Ingest-bench shape: large batched adds (keys amortize the per-op
    # pipeline cost, like bench.py's add_ints path), async-submitted so
    # the walls measure the coalescing dispatch pipeline.  Batch size
    # matters: the tracer's fixed per-op cost is sub-microsecond, so the
    # gate is only meaningful against ops carrying real ingest work.
    rounds = 800 if _TINY else 1600
    batch = 16384
    rng = np.random.default_rng(17)
    pool = rng.integers(0, 2**63, size=(64, batch), dtype=np.uint64)

    def run_workload(c):
        h = c.get_hyper_log_log("ts:hll")
        t0 = time.perf_counter()
        futs = [h.add_ints_async(pool[i % 64]) for i in range(rounds)]
        for f in futs:
            f.result(timeout=120)
        h.count()
        return time.perf_counter() - t0

    ok = True

    # -- (a) wall overhead at the default sampling stride -----------------
    # The added cost of tracing is a fixed per-op hook (begin_op's
    # counter stride, plus the full span lifecycle on every 128th op).
    # Differencing two ~100 ms walls cannot resolve a sub-millisecond
    # delta on a shared box (wall jitter here is several %), so measure
    # each factor where it is stable: the hook cost in a tight loop
    # (nanosecond-stable at best-of-N) and the per-op ingest wall from
    # the real wired client (best-of-N), then gate on their ratio.
    from redisson_tpu.trace.manager import TraceManager

    traced_cfg = Config()
    traced_cfg.use_local()
    tcfg = traced_cfg.use_trace()  # defaults: sample_every=128
    c = RedissonTPU.create(traced_cfg)
    try:
        run_workload(c)  # warm compile/caches
        c.flushall()
        wired = float("inf")
        for _ in range(3 if _TINY else 4):
            wired = min(wired, run_workload(c))
            c.flushall()
    finally:
        c.shutdown()

    probe = TraceManager(tcfg)  # same config → identical hook code path
    loops = 100_000

    def hook_loop():
        t0 = time.perf_counter()
        for _ in range(loops):
            s = probe.begin_op("HLL_ADD", "ts:hll", "", batch)
            if s is not None:  # every 128th op: full span lifecycle
                s.event("dispatched")
                s.event("staged")
                s.event("completed")
                s.finish()
        return (time.perf_counter() - t0) / loops

    hook_s = min(hook_loop() for _ in range(5))
    per_op = wired / rounds
    over = 100.0 * hook_s / per_op
    print(f"# trace-smoke[overhead]: ingest {per_op * 1e6:.1f} us/op, "
          f"trace hook {hook_s * 1e9:.0f} ns/op @1/128 -> {over:.2f}% "
          f"of wall")
    if over >= 1.0:
        print(f"#   tracing overhead {over:.2f}% >= 1% budget",
              file=sys.stderr)
        ok = False

    # -- (b) slowlog attribution under a journal-fsync stall ---------------
    root = tempfile.mkdtemp(prefix="rtpu-trace-smoke-")
    try:
        cfg = Config()
        cfg.use_local()
        pc = cfg.use_persist(os.path.join(root, "j"))
        pc.fsync = "always"
        pc.group_commit_runs = 1  # strict fsync-per-run: the seam is hot
        tc = cfg.use_trace()
        tc.sample_every = 1
        tc.slowlog_threshold_ms = 5.0
        fc = cfg.use_faults()
        # Stall the SECOND fsync: the first add warms the kernel cache so
        # compile time can't masquerade as device latency in the entry.
        fc.plan = [{"seam": "journal_fsync", "fault": "stall", "nth": 2,
                    "times": 2, "delay_s": 0.08}]
        c = RedissonTPU.create(cfg)
        try:
            h = c.get_hyper_log_log("ts:stall")
            h.add_ints(pool[0][:32])  # fsync #1: unstalled warmup
            c.trace.slowlog.reset()
            h.add_ints(pool[1][:32])  # fsync #2: stalled 80 ms
            h.count()
            entries = c.trace.slowlog.get()
            if not entries:
                print("#   stalled op never crossed the slowlog threshold",
                      file=sys.stderr)
                ok = False
            else:
                worst = max(entries, key=lambda e: e.duration_s)
                frac = worst.stages.get("journal", 0.0) / worst.duration_s
                print(f"# trace-smoke[slowlog]: slowest op '{worst.kind}' "
                      f"{worst.duration_s * 1e3:.1f} ms, worst stage "
                      f"'{worst.worst_stage}' ({100 * frac:.0f}% journal)")
                if worst.worst_stage != "journal" or frac <= 0.5:
                    print("#   stall not attributed to the journal stage",
                          file=sys.stderr)
                    ok = False
            fh = c.trace.fsync_hist.get("journal_fsync", "")
            if fh is not None and fh.count:
                print(f"# trace-smoke[fsync]: {fh.count} fsyncs, "
                      f"max {fh.max_s * 1e3:.1f} ms, "
                      f"p99 {fh.quantile(0.99) * 1e3:.1f} ms")
        finally:
            c.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return ok


def mem_smoke():
    """memstat acceptance smoke (the CPU-only CI contract for the byte-
    accounting tentpole). Three gates:

      (a) CHURN: randomized create/grow/delete/rename/flushall churn —
          verify() must report zero drift (ledger == sum of live
          Array.nbytes) at the end, and flushall must return the ledger
          to exactly zero bytes;
      (b) OVERHEAD: the ingest workload with the always-on ledger
          attached must cost < 1% wall over the same client with the
          accounting seams detached — every hook is one dict update
          under a lock the store already holds;
      (c) WATERMARK: with a 1-byte high-watermark, a memory-growing
          write must shed with RejectedError (retry-after hinted) while
          a concurrent read on the same client succeeds — graceful
          degradation, not device OOM.
    """
    import random

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.serve.errors import RejectedError

    rounds = 120 if _TINY else 400
    batch = 4096
    rng = np.random.default_rng(23)
    pool = rng.integers(0, 2**63, size=(32, batch), dtype=np.uint64)

    def make_cfg(serve=False, watermark=0):
        cfg = Config()
        cfg.use_local()
        if serve:
            cfg.use_serve()
        if watermark:
            mc = cfg.use_memstat()
            mc.high_watermark_bytes = watermark
            mc.retry_after_s = 0.5
        return cfg

    def run_workload(c):
        h = c.get_hyper_log_log("ms:hll")
        bits = c.get_bit_set("ms:bits")
        t0 = time.perf_counter()
        for i in range(rounds):
            h.add_ints(pool[i % 32])
            bits.set(i % 1999, True)
        h.count()
        return time.perf_counter() - t0

    ok = True

    # -- (a) zero drift under churn ------------------------------------
    c = RedissonTPU.create(make_cfg())
    try:
        prng = random.Random(0x4D454D)
        live = set()
        for i in range(rounds):
            roll = prng.random()
            if roll < 0.4:
                c.get_hyper_log_log("ms:h%d" % prng.randrange(8)).add(
                    b"v%d" % i)
            elif roll < 0.7:
                name = "ms:b%d" % prng.randrange(8)
                c.get_bit_set(name).set(prng.randrange(8192))
                live.add(name)
            elif roll < 0.85 and live:
                c.delete(live.pop())
            elif live:
                src = live.pop()
                dst = "ms:rn%d" % prng.randrange(4)
                if c._store.exists(src):
                    c._store.rename(src, dst)
                    live.add(dst)
        v = c.memory_verify()
        st = c.memory_stats()
        print(f"# mem-smoke[churn]: {c.memstat.events()} ledger events, "
              f"{st['keys.count']} keys, {st['dataset.bytes']} live bytes "
              f"(peak {st['peak.allocated']}), drift {v['drift_bytes']}")
        if not v["ok"]:
            print(f"#   ledger drift after churn: {v}", file=sys.stderr)
            ok = False
        c.flushall()
        after = c.memstat.live_bytes()
        if after != 0 or not c.memory_verify()["ok"]:
            print(f"#   post-flushall ledger at {after} bytes, not 0",
                  file=sys.stderr)
            ok = False
    finally:
        c.shutdown()

    # -- (b) always-on accounting overhead -----------------------------
    def best_wall(detach):
        c = RedissonTPU.create(make_cfg())
        try:
            if detach:
                c._store.accounting = None
                sketch = getattr(c._routing, "sketch", None)
                if sketch is not None and hasattr(sketch, "accounting"):
                    sketch.accounting = None
            run_workload(c)  # warm compile/caches
            c.flushall()
            best = float("inf")
            for _ in range(3 if _TINY else 2):
                best = min(best, run_workload(c))
                c.flushall()
            return best
        finally:
            c.shutdown()

    bare = best_wall(detach=True)
    wired = best_wall(detach=False)
    over = 100.0 * (wired / bare - 1.0)
    print(f"# mem-smoke[overhead]: {bare * 1e3:.1f} ms detached -> "
          f"{wired * 1e3:.1f} ms ledgered ({over:+.2f}%)")
    if over >= 1.0:
        print(f"#   ledger overhead {over:.2f}% >= 1% budget",
              file=sys.stderr)
        ok = False

    # -- (c) watermark shedding, reads flow ----------------------------
    c = RedissonTPU.create(make_cfg(serve=True, watermark=1))
    try:
        bits = c.get_bit_set("ms:wm")
        bits.set(7, True)  # admitted: the gate saw an empty ledger
        shed = None
        try:
            bits.set(8, True)  # live bytes now >= 1 -> must shed
        except RejectedError as exc:
            shed = exc
        read_ok = bits.get(7) is True and bits.cardinality() == 1
        hint = getattr(shed, "retry_after_s", 0.0)
        print(f"# mem-smoke[watermark]: write "
              f"{'shed (retry-after %.1fs)' % hint if shed else 'ADMITTED'},"
              f" concurrent read {'ok' if read_ok else 'FAILED'}")
        if shed is None or shed.reason != "memory" or hint <= 0:
            print("#   write above the watermark was not shed with a "
                  "retry-after hint", file=sys.stderr)
            ok = False
        if not read_ok:
            print("#   read failed while writes shed", file=sys.stderr)
            ok = False
    finally:
        c.shutdown()
    return ok


def cluster_smoke():
    """Cluster-tier acceptance (the CPU-only CI contract for the slot-
    sharded namespace): an N=4-shard cluster on the virtual device pool,
    randomized keyed traffic kept flowing through a LIVE slot migration.
    Gates:

      (a) LIVE MIGRATION: zero lost acks during the move, and the
          post-migration keyspace digest is identical to a no-migration
          oracle fed the same acked writes;
      (b) MOVED RETRY: ops dispatched to the old owner after the flip are
          redirected and land on the new owner — redirects observed > 0,
          every ack still arrives;
      (c) CROSS-SHARD PFMERGE: merging HLLs living on three different
          shards matches a single-shard (hashtag co-located) oracle.
    """
    import hashlib
    import random
    import shutil
    import tempfile
    import threading

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.ops.crc16 import key_slot

    n_keys = 40 if _TINY else 200
    hll_n = 300 if _TINY else 2000
    ok = True
    tmp = tempfile.mkdtemp(prefix="rtpu-cluster-smoke-")
    cfg = Config()
    cfg.use_cluster(num_shards=4, dir=os.path.join(tmp, "cl"))
    c = RedissonTPU.create(cfg)
    try:
        mgr = c.cluster
        router = mgr.router
        table = router.slot_table()

        # Keys pinned to shard 0 so one migration covers them all.
        keys, i = [], 0
        while len(keys) < n_keys:
            k = f"cs{i}"
            if table[key_slot(k)] == 0:
                keys.append(k)
            i += 1
        for k in keys:
            c.get_bucket(k).set("v0")
        move_slots = sorted({key_slot(k) for k in keys})

        # -- (a) live migration under randomized traffic ----------------
        rng = random.Random(11)
        errs, acked = [], {}
        stop = threading.Event()

        def writer():
            n = 0
            while not stop.is_set():
                k = rng.choice(keys)
                v = f"w{n}"
                try:
                    c.get_bucket(k).set(v)
                    acked[k] = v
                except Exception as exc:  # noqa: BLE001 — any lost ack fails the gate
                    errs.append((k, repr(exc)))
                n += 1

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        time.sleep(0.3)
        t0 = time.perf_counter()
        stats = mgr.migrate_slots(move_slots, 2, timeout_s=120)
        wall = time.perf_counter() - t0
        time.sleep(0.3)
        stop.set()
        wt.join(10)

        post = router.slot_table()
        flipped = all(post[s] == 2 for s in move_slots)
        # Oracle: the same acked writes on a keyspace with no migration is
        # just last-write-wins per key — the acked map IS the oracle state.
        def digest(kv):
            h = hashlib.sha256()
            for k in sorted(kv):
                h.update(k.encode() + b"=" + str(kv[k]).encode() + b";")
            return h.hexdigest()

        want = dict(acked)
        for k in keys:
            want.setdefault(k, "v0")
        got = {k: c.get_bucket(k).get() for k in keys}
        same = digest(got) == digest(want)
        print(f"# cluster-smoke[migrate]: {len(move_slots)} slots / "
              f"{len(keys)} keys moved in {wall * 1e3:.0f} ms under "
              f"{len(acked)} acked writes "
              f"(catch-up {stats['caught_up_records']}, "
              f"apply errors {stats['apply_errors']}); "
              f"lost acks {len(errs)}, digest "
              f"{'identical' if same else 'MISMATCH'}")
        if errs or not same or not flipped or stats["apply_errors"]:
            print("#   live migration gate failed", file=sys.stderr)
            ok = False

        # -- (b) deterministic MOVED retry ------------------------------
        src, tgt = mgr.shards[1], mgr.shards[3]
        mkeys, i = [], 0
        while len(mkeys) < 8:
            k = f"mr{i}"
            if post[key_slot(k)] == 1:
                mkeys.append(k)
            i += 1
        slots = sorted({key_slot(k) for k in mkeys})
        entered, release = threading.Event(), threading.Event()

        def hold():
            entered.set()
            release.wait(30)

        redirects0 = router.redirects
        bfut = src.executor.execute_barrier(hold)
        entered.wait(10)
        # Enqueued behind the barrier: the flip, then writes the router
        # still resolves to shard 1 — they dispatch post-flip, reject with
        # SlotMovedError, and the redirect worker re-lands them on shard 3.
        fflip = src.executor.execute_async("", "migrate_flip",
                                           {"slots": slots})
        wfuts = [router.execute_async(k, "set", {"value": b"m%d" % j})
                 for j, k in enumerate(mkeys)]
        tgt.adopt(slots)
        router.begin_cutover(slots)
        release.set()
        bfut.result(30)
        fflip.result(30)
        time.sleep(0.05)
        router.commit_cutover(slots, tgt.shard_id)
        moved_ok = True
        for j, f in enumerate(wfuts):
            try:
                f.result(30)
            except Exception:  # noqa: BLE001 — a lost ack fails the gate
                moved_ok = False
        redirected = router.redirects - redirects0
        landed = all(
            router.execute_sync(k, "get", None) == b"m%d" % j
            for j, k in enumerate(mkeys))
        print(f"# cluster-smoke[moved]: {redirected} redirects, "
              f"{len(mkeys)} acks "
              f"{'landed on the new owner' if moved_ok and landed else 'LOST'}")
        if redirected <= 0 or not moved_ok or not landed:
            print("#   MOVED retry gate failed", file=sys.stderr)
            ok = False

        # -- (c) cross-shard PFMERGE vs single-shard oracle --------------
        names, i = [], 0
        want_shards = [0, 1, 2]
        while len(names) < 3:
            k = f"pf{i}"
            if router.slot_table()[key_slot(k)] == want_shards[len(names)]:
                names.append(k)
            i += 1
        vals = [[b"%d:%d" % (j, v) for v in range(hll_n)] for j in range(3)]
        vals[2] = vals[0][: hll_n // 2]  # overlap exercises the max-fold
        for n, vs in zip(names, vals):
            c.get_hyper_log_log(n).add_all(vs)
        merged = c.get_hyper_log_log(names[0]).merge_with_and_count(
            *names[1:])
        oracle = c.get_hyper_log_log("{pforacle}")
        for vs in vals:
            oracle.add_all(vs)
        oracle_count = oracle.count()
        print(f"# cluster-smoke[pfmerge]: cross-shard {merged} vs "
              f"single-shard oracle {oracle_count} "
              f"({router.cross_shard_merges} register merges)")
        if merged != oracle_count or router.cross_shard_merges <= 0:
            print("#   cross-shard PFMERGE gate failed", file=sys.stderr)
            ok = False
    finally:
        _close(c)
        shutil.rmtree(tmp, ignore_errors=True)
    return ok


def mesh_smoke():
    """Mesh data-plane acceptance (PR 19 — the CPU-only CI contract for
    `data_plane="mesh"`): the same 4-shard cluster facade backed by ONE
    engine stack over a device mesh instead of N Python stacks. Gates:

      (a) MODE PARITY: a deterministic randomized mixed-kind workload
          (HLL / bitset / bloom / buckets across all shards, with a LIVE
          slot migration between halves) produces bit-identical per-op
          results AND a bit-identical state digest (raw HLL registers via
          hll_export + bitset/bloom cells via bits_export + bucket
          values) under data_plane="stacks" and data_plane="mesh";
      (b) ONE LAUNCH PER MULTI-SHARD WINDOW: a burst of concurrent adds
          spanning all shards retires through the shard-axis tape —
          observed window_launches == tape windows (1.0 launches per
          window) and the multi-shard window counter moves;
      (c) COLLECTIVE PFMERGE: merging HLLs living on different shards
          runs as a shard_map/pmax collective — count matches a hashtag
          co-located single-shard oracle and the link_bytes gauge is FLAT
          across the merge (no host register export/import round-trip).
    """
    import hashlib
    import random
    import shutil
    import tempfile

    from redisson_tpu import native as native_mod
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.ops.crc16 import key_slot

    n_hlls = 6 if _TINY else 12
    hll_n = 200 if _TINY else 1500
    n_bits = 4 if _TINY else 8
    burst_n = 1 << (10 if _TINY else 13)
    ok = True

    hnames = [f"ms:h{i}" for i in range(n_hlls)]
    bnames = [f"ms:b{i}" for i in range(n_bits)]
    knames = [f"ms:k{i}" for i in range(n_bits)]
    fname = "ms:bloom"

    def workload(c, mgr):
        """Deterministic mixed-kind workload with a live migration between
        halves; returns the per-op result list."""
        rng = random.Random(47)
        results = []
        f = c.get_bloom_filter(fname)
        f.try_init(expected_insertions=50_000, false_probability=0.01)

        def half(tag):
            for name in hnames:
                h = c.get_hyper_log_log(name)
                h.add_all([b"%s:%s:%d" % (tag, name.encode(),
                                          rng.randrange(1 << 40))
                           for _ in range(hll_n)])
                results.append(("pfcount", name, h.count()))
            for name in bnames:
                bs = c.get_bit_set(name)
                bs.set_bits([rng.randrange(1 << 16) for _ in range(64)])
                results.append(("bitcount", name, int(bs.cardinality())))
            for name in knames:
                c.get_bucket(name).set(f"{tag.decode()}:{rng.randrange(1000)}")
            added = f.add_all([b"%s:f:%d" % (tag, rng.randrange(1 << 30))
                               for _ in range(200)])
            results.append(("bfadd", fname, int(np.sum(added))))

        half(b"a")
        # Live migration between halves: every slot shard 0 owns among the
        # workload keys moves to shard 2 — both planes replay the same
        # protocol (begin/flip/adopt + journaled fence), so the second
        # half lands on the new owner in both.
        table = mgr.router.slot_table()
        move = sorted({key_slot(n) for n in hnames + bnames + knames
                       if table[key_slot(n)] == 0})
        if move:
            mgr.migrate_slots(move, 2, timeout_s=120)
        half(b"b")
        for name in knames:
            results.append(("get", name, c.get_bucket(name).get()))
        return results

    def state_digest(c, mgr):
        """Bit-identical observable-state fingerprint through the facade:
        raw HLL registers, bitset/bloom cells, bucket values."""
        h = hashlib.sha256()
        router = mgr.router
        for name in sorted(hnames):
            exported = router.execute_sync(name, "hll_export", None)
            regs = exported[0] if exported is not None else b""
            h.update(name.encode() + np.asarray(regs).tobytes() + b";")
        for name in sorted(bnames + [fname]):
            exported = router.execute_sync(name, "bits_export", None)
            if exported is not None:
                otype, cells, meta, _version = exported
                h.update(name.encode() + str(otype).encode()
                         + np.asarray(cells).tobytes() + b";")
        for name in sorted(knames):
            h.update(name.encode()
                     + repr(c.get_bucket(name).get()).encode() + b";")
        return h.hexdigest()

    def run(data_plane):
        tmp = tempfile.mkdtemp(prefix=f"rtpu-mesh-smoke-{data_plane}-")
        cfg = Config()
        cfg.use_cluster(num_shards=4, dir=os.path.join(tmp, "cl"),
                        data_plane=data_plane)
        c = RedissonTPU.create(cfg)
        try:
            results = workload(c, c.cluster)
            digest = state_digest(c, c.cluster)
            return c, tmp, results, digest
        except Exception:
            _close(c)
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    # -- (a) mode parity: stacks vs mesh ---------------------------------
    c_s, tmp_s, res_s, dig_s = run("stacks")
    _close(c_s)
    shutil.rmtree(tmp_s, ignore_errors=True)
    c_m, tmp_m, res_m, dig_m = run("mesh")
    try:
        same_res = res_s == res_m
        same_dig = dig_s == dig_m
        print(f"# mesh-smoke[parity]: {len(res_m)} op results "
              f"{'identical' if same_res else 'DIVERGED'}, state digest "
              f"{'identical' if same_dig else 'MISMATCH'} "
              f"(live migration included)")
        if not same_res or not same_dig:
            for a, b in zip(res_s, res_m):
                if a != b:
                    print(f"#   first divergence: stacks={a} mesh={b}",
                          file=sys.stderr)
                    break
            print("#   mode parity gate failed", file=sys.stderr)
            ok = False

        mgr = c_m.cluster
        backend = mgr.mesh_client._routing.sketch

        # -- (b) one fused launch per multi-shard window -----------------
        if native_mod.available():
            hs = [c_m.get_hyper_log_log(f"ms:w{i}") for i in range(4)]
            rng = np.random.default_rng(31)

            def burst():
                futs = [h.add_ints_async(rng.integers(
                    0, 2**63, burst_n, dtype=np.uint64)) for h in hs]
                for fu in futs:
                    fu.result(timeout=120)

            burst()  # warmup: compile the window shapes
            s0 = backend.ingest_stats()
            m0 = backend.counters["multi_shard_windows"]
            for _ in range(3):
                burst()
            s1 = backend.ingest_stats()
            windows = s1["tape_runs"] - s0["tape_runs"]
            launches = s1["window_launches"] - s0["window_launches"]
            multi = backend.counters["multi_shard_windows"] - m0
            lpw = launches / max(windows, 1)
            print(f"# mesh-smoke[window]: {launches} launches / "
                  f"{windows} windows = {lpw:.2f} per window "
                  f"({multi} multi-shard)")
            if windows < 1 or launches != windows or multi < 1:
                print("#   single-launch window gate failed",
                      file=sys.stderr)
                ok = False
        else:
            print("# mesh-smoke[window]: native tape encoder unavailable; "
                  "SKIP (device ingest path)", file=sys.stderr)

        # -- (c) collective PFMERGE: no host register export -------------
        table = mgr.router.slot_table()
        names, i = [], 0
        want_shards = [0, 1, 2]
        while len(names) < 3:
            k = f"mpf{i}"
            if table[key_slot(k)] == want_shards[len(names)]:
                names.append(k)
            i += 1
        vals = [[b"%d:%d" % (j, v) for v in range(hll_n)] for j in range(3)]
        vals[2] = vals[0][: hll_n // 2]  # overlap exercises the max-fold
        for nm, vs in zip(names, vals):
            c_m.get_hyper_log_log(nm).add_all(vs)
        link0 = backend.counters["link_bytes"]
        coll0 = backend.counters["collective_merges"]
        merged = c_m.get_hyper_log_log(names[0]).merge_with_and_count(
            *names[1:])
        link_moved = backend.counters["link_bytes"] - link0
        collectives = backend.counters["collective_merges"] - coll0
        oracle = c_m.get_hyper_log_log("{mpforacle}")
        for vs in vals:
            oracle.add_all(vs)
        oracle_count = oracle.count()
        print(f"# mesh-smoke[pfmerge]: cross-shard {merged} vs oracle "
              f"{oracle_count}; {collectives} collective merge(s), "
              f"link_bytes moved {link_moved}")
        if merged != oracle_count or collectives < 1 or link_moved != 0:
            print("#   collective PFMERGE gate failed", file=sys.stderr)
            ok = False
    finally:
        _close(c_m)
        shutil.rmtree(tmp_m, ignore_errors=True)
    return ok


def replica_smoke():
    """Read-replica fleet acceptance (the CPU-only CI contract for
    redisson_tpu/replica/). Gates:

      (a) BOUNDED STALENESS: randomized mixed traffic against 2 replicas —
          every replica-served read must equal the primary's state replayed
          at SOME seq inside [pick watermark, primary seq], and every
          read-your-writes read returns the tenant's own latest write;
      (b) FAILOVER: kill the primary mid-traffic; the health prober
          promotes automatically, zero acked writes are lost, and the
          promoted engine's digest is identical to a fault-free oracle
          replaying the fenced journal;
      (c) READ SCALING: compute-heavy reads (BITCOUNT over multi-Mbit
          bitsets, XLA releases the GIL) with a cache-busting trickle
          writer — throughput from 0 -> 2 replicas must reach >= 1.5x.
    """
    import json as _json
    import random
    import shutil
    import tempfile
    import threading

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.persist.journal import iter_records

    ok = True
    tmp = tempfile.mkdtemp(prefix="rtpu-replica-smoke-")

    def replicated(subdir, n=2, fsync="always", **rkw):
        cfg = Config()
        cfg.use_local()
        cfg.use_serve()
        cfg.use_persist(os.path.join(tmp, subdir)).fsync = fsync
        rc = cfg.use_replicas(n)
        for k, v in rkw.items():
            setattr(rc, k, v)
        return RedissonTPU.create(cfg)

    # -- (a) bounded staleness under randomized mixed traffic ------------
    n_steps = 300 if _TINY else 1500
    lag_bound = 8
    # Slow replica poll keeps real staleness in play: replicas trail by a
    # few seqs, so the bound (and the primary fallback) actually bites.
    c = replicated("stale", poll_interval_s=0.03, max_lag_seqs=lag_bound)
    try:
        router = c._dispatch
        keys = [f"sb{i}" for i in range(8)]
        hist = {k: [(0, None)] for k in keys}  # (seq, raw value) timeline
        rng = random.Random(0x57A1E)
        served = fallbacks = ryw_checked = violations = 0
        for step in range(n_steps):
            k = rng.choice(keys)
            if rng.random() < 0.5:
                v = f"s{step}"
                c.get_bucket(k).set(v)
                hist[k].append((c.persist.journal.last_seq,
                                _json.dumps(v).encode()))
                if rng.random() < 0.2:
                    # RYW: this tenant's next read must see its own write.
                    fut, _, _ = router.routed_read(
                        k, "get", None, max_lag=1 << 30,
                        read_your_writes=True)
                    ryw_checked += 1
                    if fut.result(30) != hist[k][-1][1]:
                        violations += 1
            else:
                fut, rep, wm = router.routed_read(
                    k, "get", None, max_lag=lag_bound,
                    read_your_writes=False)
                res = fut.result(30)
                hi = c.persist.journal.last_seq
                if rep is None:
                    fallbacks += 1
                    continue
                served += 1
                # Valid iff res is k's value at SOME seq in [wm, hi].
                valid = any(
                    val == res
                    for s, val in hist[k]
                    if s <= hi and not any(
                        s < s2 <= wm for s2, _ in hist[k])
                )
                if not valid:
                    violations += 1
        print(f"# replica-smoke[staleness]: {served} replica reads + "
              f"{fallbacks} primary fallbacks over {n_steps} steps "
              f"(lag bound {lag_bound} seqs), {ryw_checked} RYW probes; "
              f"{violations} bound violations")
        if violations or served == 0 or ryw_checked == 0:
            print("#   bounded-staleness gate failed", file=sys.stderr)
            ok = False
    finally:
        _close(c)

    # -- (b) kill-primary failover: zero acked loss, oracle digest -------
    n_fkeys = 8
    c = replicated("fail", poll_interval_s=0.005,
                   health_interval_s=0.05, health_failures=2)
    promoted_client = None
    oracle = None
    try:
        old_journal_dir = c.persist.cfg.dir
        fkeys = [f"fk{i}" for i in range(n_fkeys)]
        for k in fkeys:
            c.get_bucket(k).set("seed")
        assert c.wait_for_replicas(2, timeout_s=30.0) == 2
        attempted = {k: ["seed"] for k in fkeys}  # every value we tried
        last_acked = {k: 0 for k in fkeys}        # index into attempted[k]
        stop = threading.Event()
        rng = random.Random(0xFA11)

        def writer():
            n = 0
            while not stop.is_set():
                k = rng.choice(fkeys)
                v = f"w{n}"
                attempted[k].append(v)
                idx = len(attempted[k]) - 1
                try:
                    c.get_bucket(k).set(v)
                    last_acked[k] = idx  # fsync=always: acked == durable
                except Exception:  # noqa: BLE001 — the kill lands here
                    return
                n += 1

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        time.sleep(0.3)
        mgr = c.replicas
        c._executor.shutdown(wait=False)  # kill the primary mid-traffic
        deadline = time.time() + 30
        while mgr.promotions == 0 and time.time() < deadline:
            time.sleep(0.01)
        stop.set()
        wt.join(10)
        auto = mgr.promotions == 1
        promoted_client = mgr._promoted.client if auto else None
        lost = []
        if auto:
            for k in fkeys:
                raw = promoted_client._dispatch.execute_sync(k, "get", None)
                vals = attempted[k]
                # acked-or-newer: the promoted value must sit at/after the
                # last acked attempt (a journaled-but-unacked tail write
                # may legitimately survive; an acked one may never vanish).
                sur = [_json.dumps(v).encode() for v in
                       vals[last_acked[k]:]]
                if raw not in sur:
                    lost.append(k)
            # Fault-free oracle: a fresh engine replaying the fenced
            # journal serially IS the committed history.
            oracle = RedissonTPU.create(Config())
            for rec in iter_records(old_journal_dir):
                oracle._dispatch.execute_sync(rec.target, rec.kind,
                                              rec.payload)
            digest_same = _engine_digest(oracle) == _engine_digest(
                promoted_client)
        else:
            digest_same = False
        n_acked = sum(last_acked[k] > 0 for k in fkeys)
        print(f"# replica-smoke[failover]: auto-promote "
              f"{'fired' if auto else 'NEVER FIRED'} "
              f"({mgr.last_failover_reason!r}, "
              f"{mgr.last_failover_s * 1e3:.0f} ms), "
              f"{n_acked}/{len(fkeys)} keys had acked overwrites, "
              f"lost acks {len(lost)}, oracle digest "
              f"{'identical' if digest_same else 'MISMATCH'}, "
              f"resyncs full={mgr.full_resyncs()} "
              f"partial={mgr.partial_resyncs()}")
        if not auto or lost or not digest_same:
            print("#   failover gate failed", file=sys.stderr)
            ok = False
    finally:
        if oracle is not None:
            oracle.shutdown()
        _close(c)

    # -- (c) read scaling 0 -> 2 replicas on compute-heavy reads ---------
    # The fleet's win on the CPU proxy is twofold: BITCOUNT compute runs
    # under a released GIL, and — with fsync=always — the primary's
    # dispatcher stalls in journal fsync on every trickle write, stalls
    # the replicas' read pipelines simply don't have. Per-read compute
    # stays moderate: monster bitsets would serialize raw compute through
    # the one shared XLA threadpool and bury both effects.
    n_bits = 1 << 21
    n_targets = 2 if _TINY else 4
    phase_s = 1.5 if _TINY else 3.0
    n_threads = 4
    c = replicated("scale", poll_interval_s=0.002, max_lag_seqs=1 << 30)
    try:
        router = c._dispatch
        mgr = c.replicas
        fleet = list(mgr.replicas)
        targets = [f"bits{i}" for i in range(n_targets)]
        for t in targets:
            c.get_bit_set(t).set_range(0, n_bits, True)
        assert c.wait_for_replicas(2, timeout_s=60.0) == 2

        def warmup():
            # Compile bitset_cardinality on EVERY engine before the clock
            # starts — a replica's first read would otherwise pay its JIT
            # inside the measured window.
            for _ in range(4):
                for t in targets:
                    router.execute_sync(t, "bitset_cardinality", None,
                                        max_lag=1 << 30,
                                        read_your_writes=False)
            for rep in fleet:
                for t in targets:
                    rep.execute_read(t, "bitset_cardinality",
                                     None).result(30)

        def measure():
            warmup()
            stop_w = threading.Event()

            def trickle():
                # Bust the per-epoch BITCOUNT read caches identically in
                # both phases (replicas apply these and bump their epochs).
                i = 0
                while not stop_w.wait(0.001):
                    c.get_bit_set(targets[i % n_targets]).set_bits(
                        [i % n_bits])
                    i += 1

            counts = [0] * n_threads
            stop_r = threading.Event()

            def reader(slot):
                j = slot
                while not stop_r.is_set():
                    router.execute_sync(
                        targets[j % n_targets], "bitset_cardinality", None,
                        max_lag=1 << 30, read_your_writes=False)
                    counts[slot] += 1
                    j += 1

            wt = threading.Thread(target=trickle, daemon=True)
            wt.start()
            threads = [threading.Thread(target=reader, args=(s,),
                                        daemon=True)
                       for s in range(n_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(phase_s)
            stop_r.set()
            for t in threads:
                t.join(30)
            wall = time.perf_counter() - t0
            stop_w.set()
            wt.join(10)
            return sum(counts) / wall

        router.set_replicas([])  # phase A: the primary serves every read
        rps0 = measure()
        router.set_replicas(fleet)  # phase B: the fleet serves them
        base = router.replica_reads
        rps2 = measure()
        routed = router.replica_reads - base
        scale = rps2 / rps0 if rps0 else 0.0
        print(f"# replica-smoke[scaling]: {rps0:,.0f} reads/s with 0 "
              f"replicas -> {rps2:,.0f} with 2 ({scale:.2f}x, "
              f"{routed} replica-served, {n_targets} x {n_bits >> 20} "
              f"Mbit bitsets)")
        if scale < 1.5 or routed == 0:
            print("#   read-scaling gate failed (need >= 1.5x)",
                  file=sys.stderr)
            ok = False
    finally:
        _close(c)
        shutil.rmtree(tmp, ignore_errors=True)
    return ok


def ha_smoke():
    """Shard-level HA acceptance (the CPU-only CI contract for cluster x
    replica composition). Gates:

      (a) CHAOS UNDER MIGRATION: a cluster with per-shard replica fleets
          runs single-writer-per-key traffic plus replica-routed reads,
          recorded as an invoke/ack history. Mid-migration the SOURCE
          shard's primary is killed (the migrator resumes its suffix
          against the promotee's continuing journal) while seeded
          replica_tail partitions freeze replica watermarks. The gate:
          migration completes, the keyspace digest is identical to the
          acked-map oracle, and the history checker's verdict is clean
          (zero lost acks, bounded staleness, RYW, monotonic reads).
      (b) SPLIT-BRAIN PROBE: seeded health_probe false negatives drive a
          SPURIOUS failover of a live shard primary under unique-value
          writes. The fence makes split-brain impossible: every acked
          value lands in exactly ONE journal (the old primary's or the
          promotee's epoch journal), never both, never neither.
    """
    import hashlib
    import json as _json
    import shutil
    import tempfile
    import threading

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.fault import inject
    from redisson_tpu.ops.crc16 import key_slot
    from tools import histcheck

    rps = 1 if _TINY else 2
    n_mig_keys = 20 if _TINY else 60
    n_read_keys = 6 if _TINY else 12
    n_read_rounds = 120 if _TINY else 500
    ok = True
    tmp = tempfile.mkdtemp(prefix="rtpu-ha-smoke-")

    def ha_cluster(subdir, num_shards, health_interval_s=0.0):
        cfg = Config()
        cfg.use_cluster(num_shards=num_shards,
                        dir=os.path.join(tmp, subdir),
                        replicas_per_shard=rps)
        rc = cfg.use_replicas(rps)  # per-shard fleet tuning template
        rc.poll_interval_s = 0.002
        # 0.0 = no prober: gate (a) drives failover itself; gate (b)
        # arms probing so the injected false negatives can trip it.
        rc.health_interval_s = health_interval_s
        rc.health_failures = 2
        return RedissonTPU.create(cfg)

    def digest(kv):
        h = hashlib.sha256()
        for k in sorted(kv):
            h.update(k.encode() + b"=" + str(kv[k]).encode() + b";")
        return h.hexdigest()

    # -- (a) chaos under migration: kill + partitions, checked history ---
    c = ha_cluster("chaos", num_shards=3)
    try:
        mgr = c.cluster
        table = mgr.router.slot_table()
        mig_keys, read_keys, i = [], [], 0
        while len(mig_keys) < n_mig_keys or len(read_keys) < n_read_keys:
            k = f"ha{i}"
            owner = table[key_slot(k)]
            if owner == 0 and len(mig_keys) < n_mig_keys:
                mig_keys.append(k)
            elif owner == 1 and len(read_keys) < n_read_keys:
                read_keys.append(k)
            i += 1
        for k in mig_keys + read_keys:
            c.get_bucket(k).set("v0")
        move_slots = sorted({key_slot(k) for k in mig_keys})
        s0, s1 = mgr.shards[0], mgr.shards[1]
        deadline = time.time() + 30
        while (any(r.lag() > 0 for s in (s0, s1)
                   for r in s.replicas.replicas)
               and time.time() < deadline):
            time.sleep(0.005)

        # Seeded partitions: every fleet's tail named "replica-0" freezes
        # for a long stretch of polls. Promotion is immune (its drain
        # bypasses the tail loop) — the gate is that READS stay correct
        # via primary fallback while the frozen watermark disqualifies
        # the partitioned replica.
        inj = inject.FaultInjector(inject.FaultPlan(rules=[
            inject.FaultRule(seam="replica_tail", fault="retryable",
                             nth=20, times=400, target="replica-0"),
        ], seed=0x4A))
        inject.install(inj)

        mig_rec = histcheck.HistoryRecorder()
        read_rec = histcheck.HistoryRecorder()
        stop = threading.Event()
        logical_seq = [0]

        def mig_writer():
            # Single writer per key; a fence-raced ack is recorded as
            # unknown-fate and retried until acked, so the oracle below
            # is exact. Seqs are logical (the keys cross journals as
            # their slots migrate; lost-ack checking needs order only).
            n = 0
            while not stop.is_set():
                k = mig_keys[n % len(mig_keys)]
                v = f"m{n}"
                while not stop.is_set():
                    try:
                        c.get_bucket(k).set(v)
                        logical_seq[0] += 1
                        mig_rec.record_write("wm", k, v, logical_seq[0])
                        break
                    except Exception:  # noqa: BLE001 — fence race: fate unknown, retried (idempotent set)
                        mig_rec.record_write_unknown("wm", k, v)
                        time.sleep(0.005)
                n += 1
                time.sleep(0.001)

        def read_worker():
            # Writes + replica-routed reads on the stable shard, recorded
            # with REAL journal seqs (this shard never migrates or fails
            # over, so its seq space is the history's clock). The same
            # thread writes and reads, so recording order per tenant is
            # real-time order — what RYW checking needs.
            journal = s1.journal
            n = 0
            while not stop.is_set():
                k = read_keys[n % len(read_keys)]
                v = f"r{n}"
                try:
                    c.get_bucket(k).set(v)
                except Exception:  # noqa: BLE001 — never expected on the stable shard; surfaces as a lost ack
                    read_rec.record_write_unknown("wr", k, v)
                    n += 1
                    continue
                read_rec.record_write("wr", k, v, journal.last_seq)
                fut, _, wm = s1.dispatch.routed_read(k, "get", None)
                raw = fut.result(30)
                hi = journal.last_seq
                val = _json.loads(raw) if raw is not None else None
                read_rec.record_read("wr", k, val, watermark=wm,
                                     primary_seq=hi)
                n += 1
                if n >= n_read_rounds:
                    break

        wt = threading.Thread(target=mig_writer, daemon=True)
        rt = threading.Thread(target=read_worker, daemon=True)
        wt.start()
        rt.start()
        result = {}

        def migrate():
            try:
                result["stats"] = mgr.migrate_slots(move_slots, 2,
                                                    timeout_s=120)
            except Exception as exc:  # noqa: BLE001 — surfaced in the gate print below
                result["err"] = repr(exc)

        mt = threading.Thread(target=migrate, daemon=True)
        mt.start()
        deadline = time.time() + 30
        while not s0.guard.migrating_slots() and time.time() < deadline:
            time.sleep(0.001)
        killed = bool(s0.guard.migrating_slots())
        if killed:
            # The chaos moment: the migration source's primary dies with
            # slots mid-flight; failover must resume the suffix.
            s0.client._executor.shutdown(wait=False)
            s0.replicas.failover("ha-smoke: source kill mid-migration")
        mt.join(150)
        stop.set()
        wt.join(10)
        rt.join(10)

        migrated = "stats" in result
        post = mgr.router.slot_table()
        flipped = migrated and all(post[s] == 2 for s in move_slots)
        got = {k: c.get_bucket(k).get() for k in mig_keys}
        want = {k: recs[-1][2] for k, recs in mig_rec.writes().items()}
        for k in mig_keys:
            want.setdefault(k, "v0")
        same = digest(got) == digest(want)
        mv = histcheck.check(mig_rec, final_state=got)
        rv = histcheck.check(
            read_rec,
            final_state={k: c.get_bucket(k).get() for k in read_keys})
        snap = inj.snapshot()
        fallbacks = s1.dispatch.primary_fallbacks
        print(f"# ha-smoke[chaos]: kill mid-migration "
              f"{'fired' if killed else 'MISSED WINDOW'}, migration "
              f"{'completed' if migrated else 'FAILED: ' + result.get('err', '?')}, "
              f"{mgr.failovers()} failover(s), "
              f"{snap['injected']} replica_tail partitions, "
              f"{fallbacks} primary fallbacks | {mv.summary()} | "
              f"{rv.summary()} | digest "
              f"{'identical' if same else 'MISMATCH'}")
        if (not killed or not migrated or not flipped or not same
                or not mv.ok or not rv.ok or mgr.failovers() < 1
                or snap["injected"] == 0 or rv.reads_checked == 0):
            for issue in (mv.issues + rv.issues)[:10]:
                print(f"#   {issue}", file=sys.stderr)
            print("#   chaos-under-migration gate failed", file=sys.stderr)
            ok = False
    finally:
        inject.uninstall()
        _close(c)

    # -- (b) split-brain probe: spurious failover, exactly-once acks -----
    c = ha_cluster("brain", num_shards=2, health_interval_s=0.02)
    try:
        mgr = c.cluster
        s0 = mgr.shards[0]
        fleet = s0.replicas
        table = mgr.router.slot_table()
        bkeys = [f"sb{i}" for i in range(400)
                 if table[key_slot(f"sb{i}")] == 0][:4]
        for k in bkeys:
            c.get_bucket(k).set("seed")
        deadline = time.time() + 30
        while (any(r.lag() > 0 for r in fleet.replicas)
               and time.time() < deadline):
            time.sleep(0.005)
        old_journal_path = s0.journal.path
        # Prober with false negatives ONLY for shard 0's fleet (targeted
        # by base dir); two consecutive misses trip the failover.
        inj = inject.FaultInjector(inject.FaultPlan(rules=[
            inject.FaultRule(seam="health_probe", fault="retryable",
                             nth=3, times=2, target=fleet._base_dir),
        ], seed=0xB12A))
        acked, unknown = {}, []
        stop = threading.Event()

        def writer():
            n = 0
            while not stop.is_set():
                k = bkeys[n % len(bkeys)]
                v = f"u{n}"
                try:
                    c.get_bucket(k).set(v)
                    acked[v] = k
                except Exception:  # noqa: BLE001 — fence race: fate checked against both journals below
                    unknown.append(v)
                n += 1
                time.sleep(0.0005)

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        inject.install(inj)
        deadline = time.time() + 30
        while fleet.promotions < 1 and time.time() < deadline:
            time.sleep(0.01)
        spurious = fleet.promotions == 1
        time.sleep(0.1)  # post-failover writes land on the promotee
        stop.set()
        wt.join(10)
        dupes, missing = [], []
        if spurious:
            new_journal = fleet.primary_client._persist.journal
            new_journal.sync()  # iter_records scans files: flush first
            new_journal_path = new_journal.path
            old_vals = {_json.loads(v) for _, tgt, v in
                        histcheck.journal_writes(old_journal_path)
                        if tgt in bkeys and v is not None}
            new_vals = {_json.loads(v) for _, tgt, v in
                        histcheck.journal_writes(new_journal_path)
                        if tgt in bkeys and v is not None}
            dupes = sorted(old_vals & new_vals)
            missing = [v for v in acked
                       if v not in old_vals and v not in new_vals]
        print(f"# ha-smoke[split-brain]: spurious failover "
              f"{'fired' if spurious else 'NEVER FIRED'} "
              f"({fleet.last_failover_reason!r}), {len(acked)} acked + "
              f"{len(unknown)} unknown-fate writes; values in BOTH "
              f"journals: {len(dupes)}, acked-but-in-NEITHER: "
              f"{len(missing)}")
        if not spurious or dupes or missing or not acked:
            print("#   split-brain gate failed", file=sys.stderr)
            ok = False
    finally:
        inject.uninstall()
        _close(c)
        shutil.rmtree(tmp, ignore_errors=True)
    return ok


def race_smoke():
    """Runtime lock-order witness over the most thread-heavy suites.

    Re-runs test_ha.py / test_replica.py / test_pipeline.py in
    subprocesses with REDISSON_TPU_LOCK_WITNESS=1 and an atexit JSON dump
    per process, merges the witnessed order graphs, and gates on:

      * every subprocess suite still passes under the witness, and
      * the MERGED witnessed lock-order graph is acyclic (no two threads
        were ever seen taking witnessed locks in opposite orders).

    Also reports per-site hold-time p99 (the witness's sampled hold
    durations) and cross-checks the witnessed edges against graftlint's
    static Tier C lock-order graph — informational: the static graph is
    an over-approximation built from nested `with` blocks, the witness
    only sees orders that actually executed."""
    import subprocess
    import tempfile

    from redisson_tpu.concurrency import find_cycle, merge_snapshots

    suites = ["tests/test_ha.py", "tests/test_replica.py",
              "tests/test_pipeline.py"]
    snaps = []
    ok = True
    with tempfile.TemporaryDirectory(prefix="rtpu-race-") as td:
        for suite in suites:
            out = os.path.join(td, os.path.basename(suite) + ".witness.json")
            env = {**os.environ,
                   "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
                   "REDISSON_TPU_LOCK_WITNESS": "1",
                   "REDISSON_TPU_LOCK_WITNESS_OUT": out}
            t0 = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, "-m", "pytest", suite, "-q",
                 "-m", "not slow", "-p", "no:cacheprovider"],
                cwd=REPO, env=env, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            if proc.returncode != 0:
                print(f"# race-smoke: {suite} FAILED under the witness:\n"
                      + proc.stdout[-2000:] + proc.stderr[-2000:],
                      file=sys.stderr)
                ok = False
            if os.path.exists(out):
                with open(out) as fh:
                    snaps.append(json.load(fh))
            else:
                print(f"# race-smoke: {suite} left no witness dump",
                      file=sys.stderr)
                ok = False
            print(f"# race-smoke: {suite} done in {wall:.1f}s "
                  f"({'pass' if proc.returncode == 0 else 'FAIL'})",
                  file=sys.stderr)
    merged = merge_snapshots(snaps)
    edges = [(e["from"], e["to"]) for e in merged["edges"]]
    cyc = find_cycle(edges)
    if cyc is not None:
        print("# race-smoke: WITNESSED LOCK-ORDER CYCLE: "
              + " -> ".join(cyc), file=sys.stderr)
        ok = False
    # hold-time p99 per witnessed site, worst first
    sites = sorted(merged["sites"].items(),
                   key=lambda kv: -kv[1].get("p99_s", 0.0))
    for site, st in sites:
        print(f"#   hold {site}: holds={st['holds']} "
              f"p99={st.get('p99_s', 0.0) * 1e3:.3f}ms "
              f"max={st['max_s'] * 1e3:.3f}ms", file=sys.stderr)
    # informational cross-check vs the static Tier C graph
    try:
        from tools.graftlint.concurrency import analyze_paths

        _f, _l, static_graph = analyze_paths(
            [os.path.join(REPO, "redisson_tpu")], repo_root=REPO)
        static_edges = {(e["from"], e["to"])
                        for e in static_graph["edges"]}
        witnessed_only = sorted(set(edges) - static_edges)
        if witnessed_only:
            print(f"# race-smoke: {len(witnessed_only)} witnessed edge(s) "
                  f"the static graph missed (cross-object / callback "
                  f"orders):", file=sys.stderr)
            for a, b in witnessed_only:
                print(f"#   {a} -> {b}", file=sys.stderr)
    except Exception as exc:  # noqa: BLE001 — cross-check is informational
        print(f"# race-smoke: static cross-check skipped: {exc!r}",
              file=sys.stderr)
    result = {
        "suites": suites,
        "witnessed_edges": len(edges),
        "witnessed_threads": len(merged.get("threads", [])),
        "cycle": cyc,
        "sites": {k: v for k, v in sites[:10]},
    }
    print(json.dumps({"race_smoke": result}), flush=True)
    print(f"# race-smoke: {'PASS' if ok else 'FAIL'} — "
          f"{len(edges)} witnessed edge(s), "
          f"{'acyclic' if cyc is None else 'CYCLIC'}", file=sys.stderr)
    return ok


def wire_smoke():
    """Wire front-end acceptance smoke (the CPU-only CI contract for the
    RESP server PR):

      1. N concurrent pipelined RESP connections push a keyed PFADD/SETBIT
         workload through the wire server; every pipeline's replies must
         come back dense (zero dropped) and in submission order, checked
         with per-pipeline ECHO markers and first-write SETBIT replies
         (a nonzero previous-bit means a reply landed on the wrong
         command).
      2. The final engine digest must be bit-identical to the same
         vectors pushed through the facade directly — the wire layer may
         reorder *across* connections but must not corrupt state.
      3. Wire throughput must hold >= 0.5x the direct-facade rate: the
         RESP framing + socket hop may cost at most half the engine's
         batched throughput.
    """
    import threading

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.interop.resp_client import SyncRespClient

    n_conns = 4
    per_conn = max(_scale(3200), 250)  # commands per connection
    depth = 64                         # client pipeline depth
    n_keys = 8

    def vectors(cid, i):
        """Deterministic command #i of connection cid (both runs)."""
        if i % 2 == 0:
            key = f"wsm:hll{i % n_keys}"
            vals = [f"c{cid}i{i}k{j}" for j in range(4)]
            return ("pfadd", key, vals)
        return ("setbit", f"wsm:bits{cid}", i)

    n_warm = 256

    def warm_vectors(i):
        """Untimed JIT/codec warmup (both runs, same keys: digests still
        have to match with the warmup state folded in)."""
        if i % 2 == 0:
            return ("pfadd", f"wsm:warmh{i % n_keys}", [f"w{i}"])
        return ("setbit", "wsm:warmb", i)

    def make_client(wire):
        cfg = Config()
        cfg.use_serve()
        if wire:
            cfg.use_wire()
        return RedissonTPU(cfg)

    ok = True

    # -- wire run: N concurrent pipelined connections ------------------------
    cw = make_client(True)
    dropped = misordered = 0
    stats_lock = threading.Lock()
    try:
        def worker(cid):
            nonlocal dropped, misordered
            cli = SyncRespClient("127.0.0.1", cw.wire.port,
                                 retry_attempts=1, timeout=30.0)
            cli.connect()
            bad_drop = bad_order = 0
            try:
                for base in range(0, per_conn, depth):
                    hi = min(base + depth, per_conn)
                    marker = f"m{cid}:{base}"
                    cmds = []
                    for i in range(base, hi):
                        kind, key, payload = vectors(cid, i)
                        if kind == "pfadd":
                            cmds.append(("PFADD", key, *payload))
                        else:
                            cmds.append(("SETBIT", key, str(payload), "1"))
                    cmds.append(("ECHO", marker))
                    out = cli.pipeline(cmds)
                    if len(out) != len(cmds):
                        bad_drop += 1
                        continue
                    # Marker must be last; engine replies must be the
                    # expected ints (SETBIT on a fresh offset returns 0).
                    if out[-1] != marker.encode():
                        bad_order += 1
                    for i, r in zip(range(base, hi), out):
                        kind = vectors(cid, i)[0]
                        expect0 = (kind == "setbit")
                        if not isinstance(r, int) or (expect0 and r != 0):
                            bad_order += 1
                            break
            finally:
                cli.close()
            with stats_lock:
                dropped += bad_drop
                misordered += bad_order

        warm = SyncRespClient("127.0.0.1", cw.wire.port,
                              retry_attempts=1, timeout=30.0)
        warm.connect()
        try:
            for base in range(0, n_warm, depth):
                cmds = []
                for i in range(base, min(base + depth, n_warm)):
                    kind, key, payload = warm_vectors(i)
                    if kind == "pfadd":
                        cmds.append(("PFADD", key, *payload))
                    else:
                        cmds.append(("SETBIT", key, str(payload), "1"))
                warm.pipeline(cmds)
        finally:
            warm.close()

        threads = [threading.Thread(target=worker, args=(cid,))
                   for cid in range(n_conns)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wire_wall = time.perf_counter() - t0
        snap = cw.wire.snapshot()
        digest_wire = _engine_digest(cw)
    finally:
        cw.shutdown()

    total_cmds = n_conns * per_conn
    wire_ops = total_cmds / max(wire_wall, 1e-9)
    if dropped or misordered:
        print(f"#   wire run: {dropped} dropped / {misordered} misordered "
              f"pipeline(s)", file=sys.stderr)
        ok = False

    # -- facade run: same vectors straight into the client API ---------------
    cf = make_client(False)
    try:
        pending = []
        for i in range(n_warm):
            kind, key, payload = warm_vectors(i)
            if kind == "pfadd":
                pending.append(
                    cf.get_hyper_log_log(key).add_all_async(payload))
            else:
                pending.append(cf.get_bit_set(key).set_bits_async([payload]))
        for f in pending:
            f.result()
        pending.clear()
        t0 = time.perf_counter()
        for cid in range(n_conns):
            for i in range(per_conn):
                kind, key, payload = vectors(cid, i)
                if kind == "pfadd":
                    pending.append(
                        cf.get_hyper_log_log(key).add_all_async(payload))
                else:
                    pending.append(
                        cf.get_bit_set(key).set_bits_async([payload]))
                if len(pending) >= depth * n_conns:
                    for f in pending:
                        f.result()
                    pending.clear()
        for f in pending:
            f.result()
        facade_wall = time.perf_counter() - t0
        digest_facade = _engine_digest(cf)
    finally:
        cf.shutdown()

    facade_ops = total_cmds / max(facade_wall, 1e-9)
    ratio = wire_ops / max(facade_ops, 1e-9)

    if digest_wire != digest_facade:
        print(f"#   digest mismatch: wire {digest_wire[:16]} != "
              f"facade {digest_facade[:16]}", file=sys.stderr)
        ok = False
    if ratio < 0.5:
        print(f"#   wire throughput {wire_ops:,.0f} ops/s is "
              f"{ratio:.2f}x facade ({facade_ops:,.0f} ops/s) < 0.5x gate",
              file=sys.stderr)
        ok = False

    result = {
        "conns": n_conns,
        "commands": total_cmds,
        "pipeline_depth": depth,
        "wire_ops_per_sec": round(wire_ops, 1),
        "facade_ops_per_sec": round(facade_ops, 1),
        "throughput_ratio": round(ratio, 3),
        "dropped": dropped,
        "misordered": misordered,
        "digest_match": digest_wire == digest_facade,
        "avg_window_depth": round(snap["avg_window_depth"], 2),
        "windows_flushed": snap["windows_flushed"],
        "sheds": snap["sheds_total"],
    }
    print(json.dumps({"wire_smoke": result}), flush=True)
    print(f"# wire-smoke: {'PASS' if ok else 'FAIL'} — "
          f"{total_cmds} cmds over {n_conns} conns, "
          f"{wire_ops:,.0f} ops/s wire vs {facade_ops:,.0f} facade "
          f"({ratio:.2f}x), digest "
          f"{'identical' if result['digest_match'] else 'MISMATCH'}, "
          f"window depth {result['avg_window_depth']}", file=sys.stderr)
    return ok


#: clean-run loop-lag p99 budget for --aio-smoke, in ms. Observed ~25ms
#: p99 on a loaded CPU-CI engine (GIL contention with executor threads);
#: the gate catches order-of-magnitude regressions — an accidental sync
#: engine call or fsync landing on the wire loop, exactly what graftlint
#: G015 proves absent statically.
AIO_LAG_BUDGET_MS = 100.0


def aio_smoke():
    """Event-loop discipline smoke — the runtime half of graftlint Tier D.

    Runs the wire pipelined workload in-process under the loop-stall
    witness (REDISSON_TPU_LOOP_WITNESS=1) and gates on:

      1. clean phase: pipelined PFADD/SETBIT load (post-warmup) keeps the
         wire loop's lag p99 under AIO_LAG_BUDGET_MS, and the witness saw
         real traffic (heartbeats + WireServer callback sites);
      2. injected phase: a FaultRule(seam="wire_conn", fault="stall",
         delay_s=0.08) sleeps 80ms inside the connection read loop — the
         MERGED witness snapshot must attribute a >=60ms stall to the
         WireServer._handle coroutine (site-level attribution, not just
         "the loop was slow"), and wire.loop_stalls must tick.
    """
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.fault import inject
    from redisson_tpu.fault.inject import (FaultInjector, FaultPlan,
                                           FaultRule)
    from redisson_tpu.interop.resp_client import SyncRespClient
    from redisson_tpu.loopwitness import (ENV_FLAG, loop_witness_reset,
                                          loop_witness_snapshot,
                                          merge_loop_snapshots, uninstall)

    depth = 64
    n_cmds = max(_scale(2048), 512)
    prior_flag = os.environ.get(ENV_FLAG)
    os.environ[ENV_FLAG] = "1"  # before the wire server starts its loop

    cfg = Config()
    cfg.use_serve()
    cfg.use_wire()
    ok = True
    c = RedissonTPU(cfg)
    try:
        loop_name = f"wire:127.0.0.1:{c.wire.port}"
        cli = SyncRespClient("127.0.0.1", c.wire.port,
                             retry_attempts=1, timeout=30.0)
        cli.connect()

        def load(prefix, count):
            for base in range(0, count, depth):
                cmds = []
                for i in range(base, min(base + depth, count)):
                    if i % 2 == 0:
                        cmds.append(("PFADD", f"{prefix}h{i % 8}",
                                     f"v{i}a", f"v{i}b"))
                    else:
                        cmds.append(("SETBIT", f"{prefix}b", str(i % 512),
                                     "1"))
                cli.pipeline(cmds)

        # untimed warmup: jit + codec compile paths must not count as lag
        load("aio:warm:", 256)
        loop_witness_reset()

        # -- phase 1: clean load under the witness ------------------------
        load("aio:", n_cmds)
        clean = loop_witness_snapshot()
        cdata = clean["loops"].get(loop_name)
        if cdata is None:
            print(f"# aio-smoke: loop {loop_name!r} not in witness "
                  f"snapshot ({list(clean['loops'])})", file=sys.stderr)
            ok = False
            cdata = {"lag": {"beats": 0, "p99_s": 0.0}, "callbacks": {},
                     "stalls": []}
        lag_p99_ms = cdata["lag"]["p99_s"] * 1e3
        wire_sites = [s for s in cdata["callbacks"] if "WireServer" in s]
        if cdata["lag"]["beats"] < 10 or not wire_sites:
            print(f"# aio-smoke: witness saw no traffic (beats="
                  f"{cdata['lag']['beats']}, wire sites={wire_sites})",
                  file=sys.stderr)
            ok = False
        if lag_p99_ms > AIO_LAG_BUDGET_MS:
            print(f"# aio-smoke: clean loop-lag p99 {lag_p99_ms:.1f}ms "
                  f"over the {AIO_LAG_BUDGET_MS:.0f}ms budget",
                  file=sys.stderr)
            ok = False

        # -- phase 2: injected 80ms stall must be attributed --------------
        loop_witness_reset()
        inj = FaultInjector(FaultPlan(rules=[
            FaultRule(seam="wire_conn", fault="stall", nth=1, times=1,
                      delay_s=0.08)]))
        inject.install(inj)
        try:
            assert cli.execute("PING") == b"PONG"
        finally:
            inject.uninstall()
        stalled = loop_witness_snapshot()
        merged = merge_loop_snapshots([clean, stalled])
        mdata = merged["loops"].get(loop_name, {"stalls": []})
        attributed = [s for s in mdata["stalls"]
                      if "_handle" in s["site"] and s["ms"] >= 60.0]
        if not attributed:
            print(f"# aio-smoke: injected 80ms stall NOT attributed to "
                  f"_handle; stall log: {mdata['stalls'][:5]}",
                  file=sys.stderr)
            ok = False
        snap = c.wire.snapshot()
        if snap["loop_stalls"] < 1:
            print(f"# aio-smoke: wire.loop_stalls gauge did not tick "
                  f"({snap['loop_stalls']})", file=sys.stderr)
            ok = False
        cli.close()
    finally:
        c.shutdown()
        uninstall()
        if prior_flag is None:
            os.environ.pop(ENV_FLAG, None)
        else:
            os.environ[ENV_FLAG] = prior_flag

    result = {
        "commands": n_cmds,
        "pipeline_depth": depth,
        "lag_budget_ms": AIO_LAG_BUDGET_MS,
        "clean_lag_p99_ms": round(lag_p99_ms, 3),
        "clean_lag_beats": cdata["lag"]["beats"],
        "wire_callback_sites": len(wire_sites),
        "injected_stall_ms": 80.0,
        "attributed_stalls": attributed[:3],
        "loop_stalls_gauge": snap["loop_stalls"],
    }
    print(json.dumps({"aio_smoke": result}), flush=True)
    print(f"# aio-smoke: {'PASS' if ok else 'FAIL'} — clean lag p99 "
          f"{lag_p99_ms:.1f}ms (budget {AIO_LAG_BUDGET_MS:.0f}ms), "
          f"{len(attributed)} attributed stall(s) "
          f"{[s['site'] for s in attributed[:1]]}", file=sys.stderr)
    return ok


def geo_smoke():
    """Active-active geo-replication acceptance (redisson_tpu/geo/). Gates:

      (a) CONVERGENCE UNDER PARTITION: two sites take concurrent
          semilattice writes through a seeded geo_link partition; after
          heal + converge() their engine digests are bit-identical and
          histcheck's geo verdict is clean (zero divergent keys, zero
          missing acked writes);
      (b) FUSED APPLY: every remote mutation landed through the batched
          delta_merge_stack path (sketch counters geo_planes > 0 and
          geo_classic == 0) — replication may not fall back to per-op
          classic kernels;
      (c) WIRE EFFICIENCY: the folded/sparse link encoding ships fewer
          bytes per record than the raw journal payloads it replaces
          (link_bytes/op < raw_bytes/op on every link).
    """
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.fault import inject as _inject
    from redisson_tpu.fault.inject import (FaultInjector, FaultPlan,
                                           FaultRule)
    from redisson_tpu.geo import connect_sites, converge
    from tools.histcheck import check_geo

    n = max(_scale(2000), 400)
    ok = True
    tmp = tempfile.mkdtemp(prefix="rtpu-geo-smoke-")

    def site(sid):
        cfg = Config()
        cfg.use_local()
        cfg.use_persist(os.path.join(tmp, sid)).fsync = "always"
        g = cfg.use_geo(sid)
        g.poll_interval_s = 0.005
        g.anti_entropy_interval_s = 0.05
        return RedissonTPU.create(cfg)

    try:
        a, b = site("A"), site("B")
        try:
            connect_sites([a, b])
            # Partition the A->B direction for the first stretch of the
            # run, so heal + anti-entropy have real ground to cover.
            _inject.install(FaultInjector(FaultPlan(rules=[
                FaultRule(seam="geo_link", target="B", nth=1, times=100),
            ])))
            t0 = time.perf_counter()
            for c, tag in ((a, "A"), (b, "B")):
                c.get_hyper_log_log("geo:h").add_all(
                    [f"{tag}:{i}" for i in range(n)])
                c.get_bit_set("geo:bits").set_bits(
                    range(0 if tag == "A" else 1, n, 2))
            _inject.uninstall()
            converged = converge([a, b], timeout_s=60)
            wall_s = time.perf_counter() - t0
            if not converged:
                print("# geo-smoke: mesh never converged", file=sys.stderr)
                ok = False

            digests = {"A": _engine_digest(a), "B": _engine_digest(b)}
            verdict = check_geo(
                {sid: {"engine": d} for sid, d in digests.items()},
                acked_keys=["engine"])
            identical = digests["A"] == digests["B"] and verdict.ok
            if not identical:
                print(f"# geo-smoke: DIGEST MISMATCH {verdict.summary()}",
                      file=sys.stderr)
                ok = False

            fused = True
            for c in (a, b):
                sk = c._routing.sketch
                if not (sk.counters["geo_planes"] > 0
                        and sk.counters["geo_classic"] == 0):
                    fused = False
            if not fused:
                print("# geo-smoke: remote applies fell off the fused path",
                      file=sys.stderr)
                ok = False

            link_bytes = raw_bytes = shipped = 0
            for c in (a, b):
                for link in c.geo.links.values():
                    link_bytes += link.stats["link_bytes"]
                    raw_bytes += link.stats["raw_bytes"]
                    shipped += link.stats["shipped_records"]
            efficient = 0 < link_bytes < raw_bytes and shipped > 0
            if not efficient:
                print(f"# geo-smoke: link encoding not paying for itself "
                      f"({link_bytes}B vs {raw_bytes}B raw)",
                      file=sys.stderr)
                ok = False

            result = {
                "writes_per_site": 2 * n,
                "converged": converged,
                "converge_wall_s": round(wall_s, 3),
                "digest_identical": identical,
                "histcheck_geo": verdict.summary(),
                "fused_path": fused,
                "link_bytes_per_record": round(link_bytes / max(shipped, 1)),
                "raw_bytes_per_record": round(raw_bytes / max(shipped, 1)),
                "partitions": sum(
                    l.stats["partitions"]
                    for c in (a, b) for l in c.geo.links.values()),
            }
            print(json.dumps({"geo_smoke": result}), flush=True)
            print(f"# geo-smoke: {'PASS' if ok else 'FAIL'} — converged in "
                  f"{wall_s:.2f}s, {result['link_bytes_per_record']}B/rec "
                  f"vs {result['raw_bytes_per_record']}B raw, "
                  f"{verdict.summary()}", file=sys.stderr)
        finally:
            _inject.uninstall()
            _close(a)
            _close(b)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return ok


def contract_smoke():
    """Op-contract acceptance (graftlint Tier E + the runtime contract
    witness). Gates:

      (a) STATIC CONTRACT CLEAN: `tools.graftlint.contracts.analyze()`
          reports zero G019-G022 findings — every per-subsystem kind
          registry agrees with the OP_TABLE, every journaled write has a
          replay path, every destructive geo kind arbitrates;
      (b) NO DECLARED-BUT-DEAD CELLS: with the contract witness armed, a
          workload drives every execution surface (facade ingest, the
          RESP wire window, a two-site geo converge, crash-recovery
          replay) and the witnessed (kind x surface) matrix must cover
          every statically declared write-kind cell — plus, dynamically,
          every kind the replay journal actually holds. A declared cell
          nothing exercises is where the next registry drift hides.
    """
    import shutil
    import tempfile

    from redisson_tpu import contractwitness as cw
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.geo import connect_sites, converge
    from redisson_tpu.interop.resp_client import SyncRespClient
    from redisson_tpu.persist.journal import iter_records
    from tools.graftlint.contracts import analyze, declared_cells

    ok = True

    findings, _, stats = analyze()
    for f in findings:
        print(f"{f.file}:{f.line}: {f.rule} {f.message}", file=sys.stderr)
    if findings:
        print(f"# contract-smoke: static tier unclean "
              f"({len(findings)} finding(s))", file=sys.stderr)
        ok = False
    declared = declared_cells()

    tmp = tempfile.mkdtemp(prefix="rtpu-contract-smoke-")
    journaled = set()
    cw.arm(force=True)
    cw.contract_witness_reset()
    try:
        # -- facade + journal seed: every delta-plane write kind --------
        pdir = os.path.join(tmp, "p")
        cfg = Config()
        cfg.use_local()
        cfg.use_persist(pdir).fsync = "always"
        c = RedissonTPU.create(cfg)
        try:
            c.get_hyper_log_log("cs:h").add_all(
                [f"v{i}" for i in range(64)])
            bf = c.get_bloom_filter("cs:bf")
            bf.try_init(1024, 0.01)
            bf.add_all([f"b{i}" for i in range(64)])
            c.get_bit_set("cs:bits").set_bits(range(0, 64, 2))
            c.get_keys().delete("cs:h")
        finally:
            c.shutdown()
        journaled = {rec.kind for rec in iter_records(pdir)}

        # -- replay: recover the journal through the live executor ------
        cfg2 = Config()
        cfg2.use_local()
        cfg2.use_persist(pdir).fsync = "always"
        r = RedissonTPU.create(cfg2)
        try:
            replayed = (r.persist.last_recovery or {}).get("replayed", 0)
            if not replayed:
                print("# contract-smoke: recovery replayed nothing",
                      file=sys.stderr)
                ok = False
        finally:
            r.shutdown()

        # -- wire: one pipeline covering every staged write command -----
        wcfg = Config()
        wcfg.use_serve()
        wcfg.use_wire()
        w = RedissonTPU(wcfg)
        try:
            cli = SyncRespClient("127.0.0.1", w.wire.port,
                                 retry_attempts=1, timeout=30.0)
            cli.connect()
            try:
                cli.pipeline([
                    ("PFADD", "cs:wh", "a", "b"),
                    ("PFADD", "cs:wh2", "c"),
                    ("PFMERGE", "cs:wm", "cs:wh", "cs:wh2"),
                    ("PFCOUNT", "cs:wm"),
                    ("SETBIT", "cs:wb", "3", "1"),
                    ("SETBIT", "cs:wb", "3", "0"),
                    ("SETBIT", "cs:wb2", "1", "1"),
                    ("BITOP", "AND", "cs:wd", "cs:wb", "cs:wb2"),
                    ("GETBIT", "cs:wb", "3"),
                    ("BITCOUNT", "cs:wb"),
                    ("DEL", "cs:wb2"),
                    ("EXISTS", "cs:wb"),
                    ("KEYS", "cs:*"),
                    ("FLUSHALL",),
                ])
            finally:
                cli.close()
        finally:
            _close(w)

        # -- geo: two sites, one origin op per arbitration action -------
        def site(sid):
            scfg = Config()
            scfg.use_local()
            scfg.use_persist(os.path.join(tmp, sid)).fsync = "always"
            g = scfg.use_geo(sid)
            g.poll_interval_s = 0.005
            g.anti_entropy_interval_s = 0.05
            return RedissonTPU.create(scfg)

        a, b = site("A"), site("B")
        try:
            connect_sites([a, b])
            a.get_keys().flushall()                      # -> geo_flush
            a.get_hyper_log_log("cs:g").add_all(         # -> geo_merge
                [f"g{i}" for i in range(32)])
            gd = a.get_hyper_log_log("cs:gd")
            gd.add_all(["d1", "d2"])
            a.get_keys().delete("cs:gd")                 # -> geo_delete
            gr = a.get_hyper_log_log("cs:gr")
            gr.add_all(["r1", "r2"])
            gr.rename("cs:gr2")                          # -> geo_replace
            b.get_hyper_log_log("cs:g").add_all(["bside"])
            if not converge([a, b], timeout_s=60):
                print("# contract-smoke: geo mesh never converged",
                      file=sys.stderr)
                ok = False
        finally:
            _close(a)
            _close(b)

        snap = cw.contract_snapshot()
    finally:
        cw.uninstall()
        shutil.rmtree(tmp, ignore_errors=True)

    cells = snap.get("cells", {})
    dead = {}
    for surf, kinds in declared.items():
        missing = sorted(set(kinds) - set(cells.get(surf, {})))
        if missing:
            dead[surf] = missing
    replay_missing = sorted(journaled - set(cells.get("replay", {})))
    if dead:
        print(f"# contract-smoke: DECLARED-BUT-DEAD cells: {dead}",
              file=sys.stderr)
        ok = False
    if replay_missing:
        print(f"# contract-smoke: journaled kinds never witnessed on the "
              f"replay surface: {replay_missing}", file=sys.stderr)
        ok = False

    result = {
        "static_findings": len(findings),
        "tier_e_stats": stats,
        "declared_cells": {s: len(k) for s, k in declared.items()},
        "witnessed_cells": {s: len(k) for s, k in cells.items()},
        "journaled_kinds": sorted(journaled),
        "dead_cells": dead,
        "replay_missing": replay_missing,
    }
    print(json.dumps({"contract_smoke": result}), flush=True)
    print(f"# contract-smoke: {'PASS' if ok else 'FAIL'} — "
          f"{sum(len(k) for k in declared.values())} declared cell(s), "
          f"{sum(len(k) for k in cells.values())} witnessed, "
          f"{len(journaled)} journaled kind(s) replayed", file=sys.stderr)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, choices=sorted(CONFIGS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="BASELINE-paper sizes (slow)")
    ap.add_argument("--publish", action="store_true",
                    help="write results into BASELINE.json['published']")
    ap.add_argument("--ingest", default="auto",
                    choices=("auto", "device", "hostfold",
                             "scatter", "sort", "segment", "delta"),
                    help="sketch ingest path (auto = measured planner)")
    ap.add_argument("--trace-smoke", action="store_true",
                    help="trace acceptance: < 1% wall overhead at default "
                         "sampling vs tracing off, and a fault-injected "
                         "journal_fsync stall whose slowest SLOWLOG entry "
                         "attributes the latency to the journal stage, "
                         "then exit")
    ap.add_argument("--lint-smoke", action="store_true",
                    help="graftlint Tier A over the engine AND this bench "
                         "harness, then exit (nonzero on findings)")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="QoS serving-layer offered-load sweep (p50/p99 "
                         "queueing delay + shed rate), then exit")
    ap.add_argument("--pipeline-smoke", action="store_true",
                    help="in-flight window sweep {1,2,4}: overlap ratio, "
                         "result identity vs serial, read-cache hit rate, "
                         "then exit")
    ap.add_argument("--delta-smoke", action="store_true",
                    help="delta-ingest acceptance: bit-identical state vs "
                         "scatter, link bytes/key < 1/8 raw at the 1M-key "
                         "batch, fold/merge overlap at window 2, then exit")
    ap.add_argument("--tape-smoke", action="store_true",
                    help="window-megakernel acceptance: exactly ONE fused "
                         "launch per mixed hll/bloom/bitset window "
                         "(launches_per_window == 1), engine digest + "
                         "per-op results bit-identical to ingest=scatter, "
                         "and the pipeline smoke still green, then exit")
    ap.add_argument("--persist-smoke", action="store_true",
                    help="fsync-policy sweep {none,off,everysec,always}: "
                         "journal overhead per policy + kill-and-recover "
                         "digest identity, then exit")
    ap.add_argument("--mem-smoke", action="store_true",
                    help="memstat acceptance: zero ledger drift after "
                         "randomized churn (and after flushall), < 1% "
                         "always-on accounting overhead vs detached "
                         "seams, and watermark write-shedding with a "
                         "retry-after hint while reads flow, then exit")
    ap.add_argument("--cluster-smoke", action="store_true",
                    help="cluster-tier acceptance: N=4 shards, randomized "
                         "keyed traffic during a live slot migration — "
                         "zero lost acks + digest identical to a no-"
                         "migration oracle, deterministic MOVED retry "
                         "landing on the new owner, and cross-shard "
                         "PFMERGE matching a single-shard oracle, then "
                         "exit")
    ap.add_argument("--mesh-smoke", action="store_true",
                    help="mesh data-plane acceptance: per-op results + "
                         "state digest bit-identical between "
                         "data_plane=stacks and data_plane=mesh (live "
                         "migration included), exactly one fused launch "
                         "per multi-shard tape window, and cross-shard "
                         "PFMERGE via the shard_map collective with a "
                         "flat link_bytes gauge (no host register "
                         "export), then exit")
    ap.add_argument("--replica-smoke", action="store_true",
                    help="read-replica fleet acceptance: randomized mixed "
                         "traffic with every replica-served read inside "
                         "its staleness bound, kill-primary auto-failover "
                         "with zero acked-write loss and a fault-free "
                         "oracle digest match, and >= 1.5x read scaling "
                         "from 0 -> 2 replicas, then exit")
    ap.add_argument("--ha-smoke", action="store_true",
                    help="shard-level HA acceptance: per-shard replica "
                         "fleets under seeded chaos — source-primary kill "
                         "mid-slot-migration plus replica_tail partitions "
                         "with a clean history-checker verdict and a "
                         "digest identical to the acked-map oracle, and a "
                         "spurious health_probe failover where every "
                         "acked write lands in exactly one journal, then "
                         "exit")
    ap.add_argument("--race-smoke", action="store_true",
                    help="runtime lock-order witness: re-run the HA / "
                         "replica / pipeline suites under "
                         "REDISSON_TPU_LOCK_WITNESS=1, merge the per-"
                         "process witnessed order graphs, gate on "
                         "acyclicity, report per-site hold-time p99, and "
                         "cross-check against the static Tier C graph, "
                         "then exit")
    ap.add_argument("--wire-smoke", action="store_true",
                    help="RESP wire front-end acceptance: N concurrent "
                         "pipelined connections with zero dropped/"
                         "misordered replies, engine digest identical to "
                         "the same vectors through the facade, and wire "
                         "throughput >= 0.5x the direct-facade rate, "
                         "then exit")
    ap.add_argument("--aio-smoke", action="store_true",
                    help="event-loop discipline smoke: wire pipelined "
                         "load under REDISSON_TPU_LOOP_WITNESS=1 — clean "
                         "loop-lag p99 under budget, and an injected "
                         "80ms wire_conn stall attributed to its "
                         "_handle call site in the merged witness "
                         "snapshot, then exit")
    ap.add_argument("--geo-smoke", action="store_true",
                    help="active-active geo-replication acceptance: two "
                         "sites under a seeded geo_link partition — after "
                         "heal the engine digests are bit-identical with "
                         "a clean histcheck geo verdict, every remote "
                         "apply took the fused delta path, and the link "
                         "ships fewer bytes per record than the raw "
                         "journal payloads, then exit")
    ap.add_argument("--contract-smoke", action="store_true",
                    help="op-contract gate: graftlint Tier E static pass "
                         "must be clean, then a witnessed workload must "
                         "cover every declared (kind x surface) write "
                         "cell — facade, wire, geo, and journal replay — "
                         "with zero declared-but-dead cells, then exit")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="seeded fault injection: retry absorption digest-"
                         "identical to a fault-free oracle, uncertain-fault "
                         "rebuild + recovery digest identity, and the <1% "
                         "fault-free overhead gate, then exit")
    args = ap.parse_args()
    if not args.lint_smoke:
        from redisson_tpu.tpu_boot import acquire_devices

        acquire_devices()  # no TPU is an error unless JAX_PLATFORMS=cpu

    if args.serve_smoke:
        sys.exit(0 if serve_smoke() else 1)

    if args.pipeline_smoke:
        sys.exit(0 if pipeline_smoke() else 1)

    if args.delta_smoke:
        sys.exit(0 if delta_smoke() else 1)

    if args.tape_smoke:
        sys.exit(0 if tape_smoke() else 1)

    if args.persist_smoke:
        sys.exit(0 if persist_smoke() else 1)

    if args.race_smoke:
        sys.exit(0 if race_smoke() else 1)

    if args.contract_smoke:
        sys.exit(0 if contract_smoke() else 1)

    if args.chaos_smoke:
        sys.exit(0 if chaos_smoke() else 1)

    if args.wire_smoke:
        sys.exit(0 if wire_smoke() else 1)

    if args.aio_smoke:
        sys.exit(0 if aio_smoke() else 1)

    if args.cluster_smoke:
        sys.exit(0 if cluster_smoke() else 1)

    if args.mesh_smoke:
        sys.exit(0 if mesh_smoke() else 1)

    if args.replica_smoke:
        sys.exit(0 if replica_smoke() else 1)

    if args.geo_smoke:
        sys.exit(0 if geo_smoke() else 1)

    if args.ha_smoke:
        sys.exit(0 if ha_smoke() else 1)

    if args.mem_smoke:
        sys.exit(0 if mem_smoke() else 1)

    if args.trace_smoke:
        sys.exit(0 if trace_smoke() else 1)

    if args.lint_smoke:
        from tools.graftlint.cli import collect_tiers

        targets = [os.path.join(REPO, "redisson_tpu"),
                   os.path.join(REPO, "benchmarks"),
                   os.path.join(REPO, "bench.py")]
        dicts, tiers = collect_tiers(targets, jaxpr=False)
        for d in dicts:
            print(f"{d['file']}:{d['line']}: {d['rule']} {d['message']}")
        # Tier D must be present AND clean: the asyncio tier is the most
        # traffic-exposed subsystem, so a lint run that silently skipped
        # it (import failure, scope regression) must fail the gate.
        tier_d = tiers.get("tier_d")
        bad_tier_d = (tier_d is None or tier_d.get("modules", 0) < 1
                      or any(tier_d.get("rules", {"": 1}).values()))
        if bad_tier_d:
            print(f"# lint-smoke: tier_d missing/unclean: {tier_d}",
                  file=sys.stderr)
        # Tier E must be present AND clean over a real op universe: a
        # lint run that silently skipped the contract tier (import
        # failure, an empty OP_TABLE extraction) must fail the gate.
        tier_e = tiers.get("tier_e")
        bad_tier_e = (tier_e is None or tier_e.get("kinds", 0) < 100
                      or tier_e.get("declared_cells", 0) < 14
                      or any(tier_e.get("rules", {"": 1}).values()))
        if bad_tier_e:
            print(f"# lint-smoke: tier_e missing/unclean: {tier_e}",
                  file=sys.stderr)
        print(f"# lint-smoke: {len(dicts)} finding(s); tier_d="
              f"{tier_d}; tier_e={tier_e}", file=sys.stderr)
        sys.exit(1 if (dicts or bad_tier_d or bad_tier_e) else 0)

    global _INGEST
    _INGEST = args.ingest

    which = sorted(CONFIGS) if args.all else [args.config or 1]
    results = {}
    failures = {}
    for i in which:
        print(f"# running config {i} ...", file=sys.stderr)
        t0 = time.perf_counter()
        try:
            results[str(i)] = CONFIGS[i](args.full)
        except Exception as exc:  # noqa: BLE001 — a late config crashing
            # must not lose the finished full-scale results of earlier
            # configs; the run still exits 1 below.
            failures[str(i)] = repr(exc)
            print(f"# config {i} FAILED: {exc!r}", file=sys.stderr)
            continue
        results[str(i)]["wall_s"] = time.perf_counter() - t0
        print(json.dumps(results[str(i)]), flush=True)
        if args.publish:
            try:
                _publish(results, failures, args.full)
            except Exception as exc:  # noqa: BLE001 — keep running configs
                print(f"# publish failed: {exc!r}", file=sys.stderr)
    if args.publish and failures:
        # Record trailing failures (success paths published in-loop).
        _publish(results, failures, args.full)
    if failures:
        sys.exit(1)  # partial results are published, but signal the crash


_PROVENANCE_CACHE = None


def _provenance_meta() -> dict:
    """platform/device_kind/device count stamp so published numbers name
    the device they ran on."""
    global _PROVENANCE_CACHE
    if _PROVENANCE_CACHE is None:
        import jax

        from redisson_tpu.tpu_boot import device_stamp

        _PROVENANCE_CACHE = device_stamp(jax.devices())
    return _PROVENANCE_CACHE


def _publish(results, failures, full: bool):
    """Incrementally merge finished configs into BASELINE.json —
    atomically (temp + rename), so a mid-write kill can't truncate it."""
    path = os.path.join(REPO, "BASELINE.json")
    with open(path) as f:
        doc = json.load(f)
    doc.setdefault("published", {}).update(results)
    doc["published"]["_meta"] = {
        "full_scale": full,
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "ingest": _INGEST,
        **_provenance_meta(),
        **({"failed_configs": failures} if failures else {}),
    }
    try:
        from redisson_tpu.ingest.planner import default_planner

        table = default_planner().table()
        if table:
            doc["published"]["_meta"]["ingest_cost_table_ns_per_key"] = {
                k: {p: round(v, 2) for p, v in costs.items()}
                for k, costs in table.items()}
    except Exception as exc:  # noqa: BLE001 — table dump must not block publish
        print(f"# planner table dump failed: {exc!r}", file=sys.stderr)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, path)
    print(f"# published -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
