#!/usr/bin/env python
"""Benchmark: HLL insert throughput on one chip (north-star headline).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
vs_baseline is value / 100e6 (the BASELINE.json target of 100M inserts/sec
per chip on v5e-8).

Two rates are measured:
  * kernel  — the steady-state fused pipeline (murmur3 x64 128 -> bucket/rank
    -> register fold) on device-resident pre-split key batches with donated
    state: the raw device ceiling.
  * end-to-end — ``client.get_hyper_log_log().add_ints()`` through the
    executor's coalescing dispatcher (host numpy in, hi/lo split, pad-to-
    bucket, device transfer, futures back): what a user actually gets.
The HEADLINE is the end-to-end rate; the kernel rate and the PFMERGE(1000)
latency print on stderr and ride along as extra JSON keys.

'scatter' lowers to XLA's combining max-scatter on TPU (~9 ms per 1M-key
batch, a figure from before this round's chip runs; not measured since);
'sort' pre-compresses
the batch through jnp.sort (bitonic on TPU) and lands ~2x slower; 'segment'
is the Pallas segmented-scatter (sort + VMEM-tiled segment-max,
redisson_tpu/ingest/kernels.py). Which path a production batch takes is
decided per batch size by the measured cost table in
redisson_tpu/ingest/planner.py — the ingest[auto] report below prints the
planner's pick for this bench's batch size.

`--quick` shrinks every section to smoke-test size (2^14-key batches, tiny
roofline buffers) so the CPU run finishes in seconds — the test suite runs
it as a tier-1 smoke (tests/test_ingest.py).

Devices come from redisson_tpu.tpu_boot.acquire_devices: no TPU is an
error, and an explicit JAX_PLATFORMS=cpu run is a rehearsal whose output
is stamped "platform": "cpu".
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def bench_kernel(jax, dev, n, reps):
    """Device-resident kernel rate for both HLL insert impls.

    The whole measurement runs ON DEVICE as one jitted lax.fori_loop whose
    carry chains the register buffer, and the clock stops only when the
    final registers' scalar count reads back — nothing can be skipped.
    Each iteration XORs the batch with the loop counter so the hash chain
    is not loop-invariant (XLA would hoist it otherwise).
    """
    import functools

    import jax.numpy as jnp
    from jax import lax

    from redisson_tpu import engine
    from redisson_tpu.ingest import kernels as ingest_kernels
    from redisson_tpu.ops import hashing, hll
    from redisson_tpu.ops.u64 import U64

    rng = np.random.default_rng(42)
    keys = rng.integers(0, 2**63, size=n, dtype=np.uint64)
    packed = jax.device_put(
        keys.view(np.uint32).reshape(-1, 2), dev)

    # graftlint: allow-recompile(bench harness: compiled once per benchmark invocation by design)
    @functools.partial(jax.jit, static_argnames=("impl", "iters"))
    def insert_loop(regs, packed, impl, iters):
        p_bits = int(regs.shape[0]).bit_length() - 1

        def body(i, regs):
            # Perturb keys per iteration (defeats loop-invariant hoisting;
            # still n distinct keys per pass).
            p = packed.at[:, 0].set(packed[:, 0] ^ i.astype(jnp.uint32))
            h1, _ = hashing.murmur3_x64_128_u64(U64(p[:, 1], p[:, 0]), 0)
            if impl == "segment":
                bucket, rank = hll.bucket_rank(h1, p_bits)
                return ingest_kernels.segmented_hll_add(regs, bucket, rank)
            return hll.add_hashes(regs, h1, impl)
        regs = lax.fori_loop(0, iters, body, regs)
        return regs, hll.count(regs)

    rates = {}
    for impl in ("scatter", "sort", "segment"):
        iters = reps if impl == "scatter" else max(2, reps // 8)
        regs = jax.device_put(hll.make(), dev)
        _, est = insert_loop(regs, packed, impl, iters)
        float(est)  # compile + warm
        rate = 0.0
        for _ in range(2):  # best of two
            regs = jax.device_put(hll.make(), dev)
            t0 = time.perf_counter()
            regs, est = insert_loop(regs, packed, impl, iters)
            est = float(est)  # the only sync: after ALL iterations
            dt = time.perf_counter() - t0
            rate = max(rate, iters * n / dt)
        rates[impl] = rate
        print(
            f"# hll_add[{impl}]: {rate/1e6:.1f} M inserts/s "
            f"(device loop, {iters}x{n/1e6:.0f}M keys; est {est/1e6:.2f}M)",
            file=sys.stderr,
        )
    return rates


INGEST_CHOICE = {}


def _report_ingest_choice(n):
    """Print (and record for the JSON line) which ingest path the planner
    picks for this bench's batch size — the SAME inputs TpuBackend's
    _plan_ingest feeds it (measured device-kernel cost table, 8 B/key link
    overhead on device paths, a hostfold candidate priced from the link
    profile), so the recorded path is the one the measured batches
    actually took."""
    try:
        import jax

        from redisson_tpu import backend_tpu, native
        from redisson_tpu.ingest.planner import default_planner

        dev = jax.devices()[0]
        prof = backend_tpu.link_profile(dev)
        extra = None
        overhead = 0.0
        if native.available() and n >= backend_tpu.HOSTFOLD_MIN_KEYS:
            overhead = prof.transfer_ns_per_byte * 8
            extra = {"hostfold": prof.fold_ns_per_key
                     + prof.transfer_ns_per_byte * 16384 / max(n, 1)}
        plan = default_planner().plan(
            "hll", n, extra_costs=extra, device_overhead=overhead)
        INGEST_CHOICE.update(
            path=plan.path,
            costs_ns_per_key={k: round(v, 2) for k, v in plan.costs.items()},
            transfer_mb_per_s=round(1e3 / prof.transfer_ns_per_byte, 1),
            fold_mkeys_per_s=round(1e3 / prof.fold_ns_per_key, 1),
        )
        costs = ", ".join(
            f"{k} {v}" for k, v in INGEST_CHOICE["costs_ns_per_key"].items())
        print(
            f"# ingest[auto] -> {INGEST_CHOICE['path']} "
            f"(ns/key: {costs}): link "
            f"{INGEST_CHOICE['transfer_mb_per_s']} MB/s, native fold "
            f"{INGEST_CHOICE['fold_mkeys_per_s']} M keys/s",
            file=sys.stderr,
        )
    except Exception as exc:  # noqa: BLE001
        print(f"# ingest probe failed: {exc!r}", file=sys.stderr)


def bench_end_to_end(n, reps):
    """Client-path rate: add_ints() through the coalescing executor.

    Round-2 postmortem (VERDICT r2 weak #1): the client path was 6 M/s
    against a 59 G/s kernel because the dispatcher synced the device per
    chunk (`bool(changed)`) and the client copied hi/lo splits per batch.
    Round 3 ships the keys' raw uint32 view (zero host copies), masks
    validity on device, resolves futures on a completer thread with D2H
    copies started at dispatch, and — when the link probe says transfers
    are the bottleneck — folds each run
    into 16 KB of registers natively and ships the sketch instead of the
    keys (backend_tpu hostfold; same registers, golden-tested).
    """
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_trace().sample_every = 1  # few large ops: trace them all
    client = RedissonTPU.create(cfg)
    try:
        h = client.get_hyper_log_log("bench:e2e")
        rng = np.random.default_rng(7)
        _report_ingest_choice(n)
        batches = [
            rng.integers(0, 2**63, size=n, dtype=np.uint64) for _ in range(reps)
        ]
        h.add_ints(batches[0])  # warmup / compile
        rate = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            futs = [h.add_ints_async(b) for b in batches[1:]]
            for f in futs:
                f.result(timeout=120)
            dt = time.perf_counter() - t0
            rate = max(rate, (reps - 1) * n / dt)
        err = abs(h.count() - reps * n) / (reps * n)
        th = client.trace.hist.merged("hll_add")
        pcts = ({k: round(v * 1e6, 1) for k, v in th.percentiles().items()
                 if k in ("p50", "p95", "p99")} if th.count else {})
        print(
            f"# end-to-end add_ints: {rate/1e6:.1f} M inserts/s; "
            f"card err {err*100:.2f}%; "
            f"p50/p95/p99 {pcts.get('p50', 0):.0f}/{pcts.get('p95', 0):.0f}/"
            f"{pcts.get('p99', 0):.0f} us",
            file=sys.stderr,
        )
        return rate, err, pcts
    finally:
        client.shutdown()


def bench_host_budget(jax, dev, n):
    """Quantify the host budget per 1M-key batch (VERDICT r2 weak #7): what
    the client path spends on prep (uint32 view), transfer (8 B/key DMA),
    kernel dispatch, and a device sync round-trip. kernel-vs-client gaps
    must be explainable from these four numbers."""
    from redisson_tpu import engine
    from redisson_tpu.ops import hll

    rng = np.random.default_rng(3)
    keys = rng.integers(0, 2**63, size=n, dtype=np.uint64)

    t0 = time.perf_counter()
    for _ in range(10):
        packed = np.ascontiguousarray(keys, np.uint64).view(np.uint32).reshape(-1, 2)
    prep_us = (time.perf_counter() - t0) / 10 * 1e6

    xs = []
    t0 = time.perf_counter()
    for _ in range(8):
        xs.append(jax.device_put(packed, dev))
    for x in xs:
        x.block_until_ready()
    transfer_us = (time.perf_counter() - t0) / 8 * 1e6

    regs = jax.device_put(hll.make(), dev)
    regs, ch = engine.hll_add_packed(regs, packed, np.int32(n), "scatter", 0)
    regs.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(8):
        regs, ch = engine.hll_add_packed(regs, packed, np.int32(n), "scatter", 0)
    dispatch_us = (time.perf_counter() - t0) / 8 * 1e6
    regs.block_until_ready()

    t0 = time.perf_counter()
    for _ in range(5):
        bool(ch)
    sync_us = (time.perf_counter() - t0) / 5 * 1e6

    budget = {
        "prep_us_per_batch": round(prep_us, 1),
        "transfer_us_per_batch": round(transfer_us, 1),
        "dispatch_us_per_batch": round(dispatch_us, 1),
        "sync_us_per_roundtrip": round(sync_us, 1),
        "batch_keys": n,
    }
    print(
        f"# host budget /{n/1e6:.0f}M-key batch: prep {prep_us:.0f} us, "
        f"transfer {transfer_us:.0f} us ({keys.nbytes/transfer_us:.0f} MB/s), "
        f"dispatch {dispatch_us:.0f} us, sync {sync_us:.0f} us",
        file=sys.stderr,
    )
    return budget


def bench_device_ingest(jax, dev, n, reps):
    """Client-path rate with device-resident input (add_device_async):
    executor dispatch + kernels with no host staging or transfer — what a
    user whose keys are produced on-chip gets, and the client-stack ceiling
    the host path converges to as transfer bandwidth allows."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.models.object import pack_u64

    client = RedissonTPU.create()
    try:
        h = client.get_hyper_log_log("bench:dev")
        rng = np.random.default_rng(9)
        batches = [
            jax.device_put(
                pack_u64(rng.integers(0, 2**63, n, np.uint64)), dev)
            for _ in range(reps)
        ]
        for b in batches:
            b.block_until_ready()
        h.add_device(batches[0])  # warmup / compile
        rate = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            futs = [h.add_device_async(b) for b in batches[1:]]
            for f in futs:
                f.result(timeout=120)
            dt = time.perf_counter() - t0
            rate = max(rate, (reps - 1) * n / dt)
        err = abs(h.count() - reps * n) / (reps * n)
        print(
            f"# device-resident add_device: {rate/1e6:.1f} M inserts/s; "
            f"card err {err*100:.2f}%",
            file=sys.stderr,
        )
        return rate
    finally:
        client.shutdown()


def bench_delta_ingest(n, reps):
    """Client-path rate through the delta ingest tentpole (ingest="delta"):
    each run folds on the host into a 16 KB register image, ships the
    plane instead of 8 B/key, and retires every plane staged in a pipeline
    window through ONE fused elementwise merge. Because the retire kernel
    is an elementwise max (no combining scatter), its honest ceiling is
    the HBM-bandwidth bound — `binding` in the report flips from the raw
    path's scatter-issue to hbm. Also reports delta_bytes_per_key (the
    link-compression headline: 16384/nkeys for an HLL plane) and
    merge_launches/delta_runs (1.0 = one fused launch per window)."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config, TpuConfig

    client = RedissonTPU.create(Config(tpu=TpuConfig(ingest="delta")))
    try:
        sketch = client._routing.sketch
        hs = [client.get_hyper_log_log(f"bench:delta:{i}") for i in range(4)]
        rng = np.random.default_rng(13)
        batches = [
            rng.integers(0, 2**63, size=n, dtype=np.uint64)
            for _ in range(reps)
        ]
        hs[0].add_ints(batches[0])  # warmup / compile
        rate = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            futs = [hs[i % len(hs)].add_ints_async(b)
                    for i, b in enumerate(batches[1:])]
            for f in futs:
                f.result(timeout=120)
            dt = time.perf_counter() - t0
            rate = max(rate, (reps - 1) * n / dt)
        stats = sketch.ingest_stats()
        launches_per_run = (stats["merge_launches"]
                            / max(stats["delta_runs"], 1))
        out = {
            "delta_inserts_per_sec": round(rate, 1),
            "delta_bytes_per_key": round(stats["delta_bytes_per_key"], 3),
            "raw_bytes_per_key": round(
                stats["raw_bytes"] / max(stats["delta_keys"], 1), 3),
            "merge_launches_per_run": round(launches_per_run, 2),
            "delta_runs": stats["delta_runs"],
            "launches_per_window": round(stats["launches_per_window"], 2),
            "launch_us_per_window": round(stats["launch_us_per_window"], 1),
            "binding": "hbm",  # elementwise merge: no scatter-issue bound
        }
        print(
            f"# delta ingest: {rate/1e6:.1f} M inserts/s; "
            f"{out['delta_bytes_per_key']} B/key shipped "
            f"(raw {out['raw_bytes_per_key']}), "
            f"{launches_per_run:.2f} merge launches/run; binding=hbm",
            file=sys.stderr,
        )
        return out
    finally:
        client.shutdown()


def bench_tape_window(n, reps):
    """Window megakernel vs chunked delta: per-window DISPATCH cost.

    The roofline section pins the ingest ceiling at scatter-ISSUE —
    per-launch overhead, not HBM bandwidth. The tape path attacks that
    term directly: the whole mixed hll+bloom+bitset window is encoded
    into one command tape and retired by ONE fused launch. This bench
    runs the same mixed-window burst under ingest="delta" (gather +
    per-plane decode + merge + writeback launch train) and
    ingest="tape", and reports the OBSERVED `launches_per_window` /
    `launch_us_per_window` for both (acceptance: tape == 1 launch and
    >= 4x lower issue time per window)."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config, TpuConfig

    def run(ingest):
        client = RedissonTPU.create(Config(tpu=TpuConfig(ingest=ingest)))
        try:
            sketch = client._routing.sketch
            h = client.get_hyper_log_log("bench:tape:hll")
            f = client.get_bloom_filter("bench:tape:bloom")
            f.try_init(expected_insertions=200_000, false_probability=0.01)
            bs = client.get_bit_set("bench:tape:bits")
            rng = np.random.default_rng(17)

            def burst():
                futs = [
                    h.add_ints_async(
                        rng.integers(0, 2**63, n, dtype=np.uint64)),
                    f.add_ints_async(rng.integers(
                        0, 2**62, max(n // 4, 1), dtype=np.uint64)),
                    bs.set_bits_async(
                        rng.integers(0, 1 << 20, 256, dtype=np.int64)),
                ]
                for fu in futs:
                    fu.result(timeout=120)

            burst()  # warmup: compile every shape the window will see
            s0 = sketch.ingest_stats()
            t0 = time.perf_counter()
            for _ in range(max(reps - 1, 1)):
                burst()
            dt = time.perf_counter() - t0
            s1 = sketch.ingest_stats()
            windows = (s1["delta_runs"] + s1["tape_runs"]
                       - s0["delta_runs"] - s0["tape_runs"])
            launches = s1["window_launches"] - s0["window_launches"]
            us = s1["launch_us"] - s0["launch_us"]
            return {
                "launches_per_window": round(launches / max(windows, 1), 2),
                "launch_us_per_window": round(us / max(windows, 1), 1),
                "windows": windows,
                "inserts_per_sec": round(max(reps - 1, 1) * n / dt, 1),
            }
        finally:
            client.shutdown()

    delta = run("delta")
    tape = run("tape")
    speedup = (delta["launch_us_per_window"]
               / max(tape["launch_us_per_window"], 1e-9))
    print(
        f"# tape window: {tape['launches_per_window']} launches/window "
        f"@ {tape['launch_us_per_window']} us (delta: "
        f"{delta['launches_per_window']} @ "
        f"{delta['launch_us_per_window']} us) -> "
        f"{speedup:.1f}x lower issue cost",
        file=sys.stderr,
    )
    return {"delta": delta, "tape": tape,
            "launch_us_speedup": round(speedup, 2)}


def bench_roofline(jax, dev, n, kernel_rate, segment_rate=0.0, quick=False):
    """Roofline for the HLL insert kernel (VERDICT r4 weak #6): relate the
    measured inserts/s to what the chip could do, so the number has a
    denominator.

    Two candidate ceilings, both measured on THIS device:

      * HBM-bandwidth bound — minimum traffic is the 8 B/key input read
        (registers are 16 KB and live in cache/VMEM); ceiling =
        measured_copy_BW / 8.
      * scatter-issue bound — TPU lowers a combining max-scatter over
        colliding indices to a serialized update loop; ceiling = the rate of
        a bare scatter-max with precomputed indices (no hash work).

    The binding (smaller) ceiling is the roofline; pct_of_roofline =
    kernel_rate / roofline. On TPU the scatter-issue bound binds by ~2-3
    orders of magnitude — which is exactly why SURVEY §7 lists scatter
    contention as the hard part and why the sorted/segment variant exists.
    """
    import functools

    import jax.numpy as jnp
    from jax import lax

    from redisson_tpu.ops import hll

    # -- effective HBM copy bandwidth (device loop, read+write) ------------
    buf = jax.device_put(
        np.zeros(1 << (20 if quick else 24), np.float32), dev)  # 4 / 64 MB

    @jax.jit
    def copy_loop(x, iters):
        def body(i, x):
            return x + jnp.float32(1.0)  # read + write the full buffer
        return lax.fori_loop(0, iters, body, x)

    iters = 4 if quick else 32
    out = copy_loop(buf, iters)
    out.block_until_ready()
    t0 = time.perf_counter()
    out = copy_loop(buf, iters)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    hbm_gb_s = 2 * buf.nbytes * iters / dt / 1e9
    bw_bound = hbm_gb_s * 1e9 / 8.0  # 8 B read per key

    # -- bare scatter-max issue rate (no hashing) --------------------------
    rng = np.random.default_rng(11)
    idx = jax.device_put(
        rng.integers(0, hll.M, size=n, dtype=np.int32), dev)
    vals = jax.device_put(
        rng.integers(1, 50, size=n, dtype=np.uint8), dev)

    # graftlint: allow-recompile(bench harness: compiled once per benchmark invocation by design)
    @functools.partial(jax.jit, static_argnames=("iters",))
    def scatter_loop(regs, idx, vals, iters):
        def body(i, regs):
            # rotate indices per iteration so the loop body isn't invariant
            j = (idx + i) & (hll.M - 1)
            return regs.at[j].max(vals)
        regs = lax.fori_loop(0, iters, body, regs)
        return regs, regs.max()

    reps = 8
    regs = jax.device_put(np.zeros(hll.M, np.uint8), dev)
    _, mx = scatter_loop(regs, idx, vals, reps)
    int(mx)  # compile + warm
    regs = jax.device_put(np.zeros(hll.M, np.uint8), dev)
    t0 = time.perf_counter()
    _, mx = scatter_loop(regs, idx, vals, reps)
    int(mx)
    dt = time.perf_counter() - t0
    scatter_bound = reps * n / dt

    roofline = min(bw_bound, scatter_bound)
    bound = "scatter-issue" if scatter_bound <= bw_bound else "hbm-bandwidth"
    pct = 100.0 * kernel_rate / roofline if roofline else 0.0
    # The segmented-scatter kernel (ingest/kernels.py) sidesteps the
    # serialized scatter-issue bound, so its honest ceiling is the
    # HBM-bandwidth bound alone.
    pct_seg = 100.0 * segment_rate / bw_bound if bw_bound else 0.0
    print(
        f"# roofline: hbm {hbm_gb_s:.0f} GB/s -> {bw_bound/1e6:.0f} M/s; "
        f"bare scatter {scatter_bound/1e6:.1f} M/s; binding={bound}; "
        f"kernel at {pct:.0f}% of roofline"
        f"; segment at {pct_seg:.0f}% of hbm bound",
        file=sys.stderr,
    )
    return {
        "roofline_inserts_per_sec": round(roofline, 1),
        "pct_of_roofline": round(pct, 1),
        "pct_of_roofline_segment": round(pct_seg, 1),
        "roofline_bound": bound,
        "hbm_copy_gb_per_s": round(hbm_gb_s, 1),
        "scatter_issue_inserts_per_sec": round(scatter_bound, 1),
    }


def bench_read_cache(n, reps=20):
    """Epoch-stamped read cache (PR 4): hll count() roundtrip latency with
    the cache cold (each read preceded by a write, so the epoch moved and
    the count pays the full device sync) vs warm (repeated reads at one
    epoch, served host-side). The before/after sync_us_per_roundtrip pair
    is the cost the cache removes — the client-side-caching analogue of
    Redisson's RLocalCachedMap."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_trace().sample_every = 1
    client = RedissonTPU.create(cfg)
    try:
        h = client.get_hyper_log_log("bench:cache")
        rng = np.random.default_rng(5)
        h.add_ints(rng.integers(0, 2**63, size=n, dtype=np.uint64))
        h.count()  # compile + warm

        miss_us, hit_us = [], []
        for i in range(reps):
            h.add_ints(np.array([i], dtype=np.uint64))  # bump the epoch
            t0 = time.perf_counter()
            h.count()  # miss: full device roundtrip
            miss_us.append((time.perf_counter() - t0) * 1e6)
            t0 = time.perf_counter()
            h.count()  # hit: same epoch, memoized
            hit_us.append((time.perf_counter() - t0) * 1e6)
        before = float(np.median(miss_us))
        after = float(np.median(hit_us))
        out = {
            "sync_us_per_roundtrip_before": round(before, 1),
            "sync_us_per_roundtrip_after": round(after, 1),
            "speedup": round(before / after, 1) if after else 0.0,
        }
        cache = getattr(
            getattr(client._routing, "sketch", None), "read_cache", None)
        if cache is not None:
            out["hit_ratio"] = round(cache.stats()["hit_ratio"], 3)
        th = client.trace.hist.merged("hll_count")
        if th.count:
            out["latency_us"] = {
                k: round(v * 1e6, 1) for k, v in th.percentiles().items()
                if k in ("p50", "p95", "p99")}
        print(
            f"# hll_count_cached: {before:.0f} us uncached -> {after:.0f} us "
            f"cached per roundtrip ({out['speedup']}x; hit ratio "
            f"{out.get('hit_ratio', 'n/a')})",
            file=sys.stderr,
        )
        return out
    finally:
        client.shutdown()


def bench_memstat(n, sketches=64):
    """HBM byte accounting (memstat tentpole): run a mixed ingest
    workload, then read the always-on ledger — live device bytes,
    scratch/staging overhead, and bytes per addressable key — and check
    the exact invariant (ledger == sum of live Array.nbytes) held."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    client = RedissonTPU.create(Config())
    try:
        rng = np.random.default_rng(29)
        per = max(1, n // sketches)
        for i in range(sketches):
            h = client.get_hyper_log_log(f"bench:mem:h{i}")
            h.add_ints(rng.integers(0, 2**63, size=per, dtype=np.uint64))
        bits = client.get_bit_set("bench:mem:bits")
        bits.set(n % 65536, True)
        stats = client.memory_stats()
        verify = client.memory_verify()
        totals = client.memstat.meter_totals()
        out = {
            "hbm_live_bytes": stats["dataset.bytes"],
            "hbm_scratch_bytes": totals["scratch"] + totals["staging"],
            "bytes_per_key": stats["keys.bytes-per-key"],
            "hbm_peak_bytes": stats["peak.allocated"],
            "drift_bytes": verify["drift_bytes"],
        }
        print(
            f"# memstat: {out['hbm_live_bytes']} live HBM bytes across "
            f"{stats['keys.count']} keys ({out['bytes_per_key']} B/key, "
            f"scratch {out['hbm_scratch_bytes']}), drift "
            f"{out['drift_bytes']}",
            file=sys.stderr,
        )
        return out
    finally:
        client.shutdown()


def bench_journal_overhead(rounds=200, reps=3):
    """Write-ahead journal tax (PR 6): the batched-insert path with the
    everysec journal hooked into the dispatcher vs the same client without
    persistence. Async submits keep the dispatch window (>= 2) full so
    journal appends overlap device work; best-of-reps squeezes out
    scheduler jitter. The acceptance budget for this number is < 10%."""
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    batch = 64
    ints = np.random.default_rng(11).integers(
        0, 2**63, size=(rounds, batch), dtype=np.uint64)

    def timed(client):
        h = client.get_hyper_log_log("bench:wal")
        m = client.get_map("bench:walm")
        best = float("inf")
        for _ in range(reps):
            pend = []
            t0 = time.perf_counter()
            for i in range(rounds):
                pend.append(h.add_ints_async(ints[i]))
                pend.append(m.put_async(f"f{i}", i))
                if len(pend) >= 8:
                    for f in pend:
                        f.result(timeout=60)
                    pend.clear()
            for f in pend:
                f.result(timeout=60)
            best = min(best, time.perf_counter() - t0)
        return best

    root = tempfile.mkdtemp(prefix="rtpu-bench-wal-")
    try:
        base_client = RedissonTPU.create()
        try:
            timed(base_client)  # warm compile/caches
            base = timed(base_client)
        finally:
            base_client.shutdown()

        cfg = Config()
        cfg.use_persist(root).fsync = "everysec"
        wal_client = RedissonTPU.create(cfg)
        try:
            timed(wal_client)
            wal = timed(wal_client)
        finally:
            wal_client.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    pct = 100.0 * (wal / base - 1.0)
    print(f"# journal_overhead: {base * 1e3:.1f} ms bare -> {wal * 1e3:.1f} ms "
          f"with everysec journal ({pct:+.1f}%)", file=sys.stderr)
    return pct


def bench_lock_witness(rounds=200, reps=3):
    """Lock-order witness tax (PR 15): the batched-insert path with a
    journal attached — the workload that hammers the hottest witnessed
    locks (executor._lock, _InflightRun.lock, journal._io: ~1.9k
    acquisitions per 200-round pass) — on a client whose locks were built
    under
    REDISSON_TPU_LOCK_WITNESS=1 vs the same client with plain primitives.
    The witness is opt-in diagnostics; its budget is < 3% so it stays
    usable under load. Zero-cost when disabled: make_lock returns a plain
    threading.Lock, so the 'off' side IS the production configuration.
    Both clients live side by side and single passes alternate plain/
    witnessed (best-of-reps each), so scheduler and fsync-thread drift
    hits both sides instead of biasing whichever ran second."""
    import os
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.concurrency import witness_reset
    from redisson_tpu.config import Config

    batch = 64
    ints = np.random.default_rng(23).integers(
        0, 2**63, size=(rounds, batch), dtype=np.uint64)

    def one_pass(client, tag):
        h = client.get_hyper_log_log(f"bench:wit:{tag}")
        m = client.get_map(f"bench:witm:{tag}")
        pend = []
        t0 = time.perf_counter()
        for i in range(rounds):
            pend.append(h.add_ints_async(ints[i]))
            pend.append(m.put_async(f"f{i}", i))
            if len(pend) >= 8:
                for f in pend:
                    f.result(timeout=60)
                pend.clear()
        for f in pend:
            f.result(timeout=60)
        return time.perf_counter() - t0

    def make_client(witness: bool, root: str):
        old = os.environ.get("REDISSON_TPU_LOCK_WITNESS")
        if witness:
            os.environ["REDISSON_TPU_LOCK_WITNESS"] = "1"
        else:
            os.environ.pop("REDISSON_TPU_LOCK_WITNESS", None)
        try:
            cfg = Config()
            # "off": journal appends still take Journal._io on the hot
            # path, but no everysec fsync tick randomly lands inside a
            # ~300ms timed pass (that tick is pure variance here; the
            # journal tax itself is bench_journal_overhead's number).
            cfg.use_persist(root).fsync = "off"
            return RedissonTPU.create(cfg)
        finally:
            if old is None:
                os.environ.pop("REDISSON_TPU_LOCK_WITNESS", None)
            else:
                os.environ["REDISSON_TPU_LOCK_WITNESS"] = old

    root_a = tempfile.mkdtemp(prefix="rtpu-bench-wit-a-")
    root_b = tempfile.mkdtemp(prefix="rtpu-bench-wit-b-")
    base = wit = float("inf")
    try:
        plain_client = make_client(False, root_a)
        try:
            wit_client = make_client(True, root_b)
            try:
                one_pass(plain_client, "p")  # warm compile/caches
                one_pass(wit_client, "w")
                for _ in range(max(2, reps)):
                    base = min(base, one_pass(plain_client, "p"))
                    wit = min(wit, one_pass(wit_client, "w"))
            finally:
                wit_client.shutdown()
        finally:
            plain_client.shutdown()
    finally:
        witness_reset()
        shutil.rmtree(root_a, ignore_errors=True)
        shutil.rmtree(root_b, ignore_errors=True)

    pct = 100.0 * (wit / base - 1.0)
    print(f"# lock_witness_overhead: {base * 1e3:.1f} ms plain -> "
          f"{wit * 1e3:.1f} ms witnessed ({pct:+.1f}%; budget < 3%)",
          file=sys.stderr)
    return pct


def bench_contract_witness(rounds=200, reps=3):
    """Contract-coverage witness tax (PR 20): the batched-insert path —
    the workload that hammers the executor's enqueue funnel, where the
    witness tap lives — with the witness armed vs disarmed. The disarmed
    side is ONE module-global probe (`RECORD is None`) per op, i.e. the
    production configuration; the armed side adds a thread-local dict
    increment per op. Budget < 1%: the witness is an always-on candidate
    for CI smokes, so it must be invisible in the enqueue path. Both
    clients live side by side and single passes alternate off/on
    (best-of-reps each), so scheduler drift hits both sides instead of
    biasing whichever ran second."""
    import shutil
    import tempfile

    from redisson_tpu import contractwitness as cw
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    batch = 64
    ints = np.random.default_rng(29).integers(
        0, 2**63, size=(rounds, batch), dtype=np.uint64)

    def one_pass(client, tag, armed):
        cw.arm(force=True) if armed else cw.disarm()
        h = client.get_hyper_log_log(f"bench:cw:{tag}")
        m = client.get_map(f"bench:cwm:{tag}")
        pend = []
        t0 = time.perf_counter()
        for i in range(rounds):
            pend.append(h.add_ints_async(ints[i]))
            pend.append(m.put_async(f"f{i}", i))
            if len(pend) >= 8:
                for f in pend:
                    f.result(timeout=60)
                pend.clear()
        for f in pend:
            f.result(timeout=60)
        dt = time.perf_counter() - t0
        cw.disarm()
        return dt

    root_a = tempfile.mkdtemp(prefix="rtpu-bench-cw-a-")
    root_b = tempfile.mkdtemp(prefix="rtpu-bench-cw-b-")
    base = wit = float("inf")
    try:
        off_client = RedissonTPU.create(
            _persist_cfg(root_a))
        try:
            on_client = RedissonTPU.create(
                _persist_cfg(root_b))
            try:
                one_pass(off_client, "p", False)  # warm compile/caches
                one_pass(on_client, "w", True)
                for _ in range(max(2, reps)):
                    base = min(base, one_pass(off_client, "p", False))
                    wit = min(wit, one_pass(on_client, "w", True))
            finally:
                on_client.shutdown()
        finally:
            off_client.shutdown()
    finally:
        cw.uninstall()
        shutil.rmtree(root_a, ignore_errors=True)
        shutil.rmtree(root_b, ignore_errors=True)

    pct = 100.0 * (wit / base - 1.0)
    print(f"# contract_witness_overhead: {base * 1e3:.1f} ms off -> "
          f"{wit * 1e3:.1f} ms armed ({pct:+.1f}%; budget < 1%)",
          file=sys.stderr)
    return pct


def _persist_cfg(root):
    from redisson_tpu.config import Config

    cfg = Config()
    # fsync "off" for the same reason as bench_lock_witness: an everysec
    # fsync tick landing inside one ~300ms timed pass is pure variance.
    cfg.use_persist(root).fsync = "off"
    return cfg


def bench_fault(rounds=200, reps=3):
    """Fault-subsystem numbers (PR 8): fault_overhead_pct — the batched-
    insert workload with taxonomy + injection seams + watchdog + rebuild
    guard all wired but idle, vs a bare client (budget < 1%: the disabled
    `fire()` seam is one module-global read, the enqueue guard two empty-
    set checks) — and fault_rebuild_s, the wall time of one self-healing
    HBM rebuild (quarantine -> snapshot+journal re-materialize -> resume)
    after an injected device-loss fault."""
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    batch = 64
    ints = np.random.default_rng(23).integers(
        0, 2**63, size=(rounds, batch), dtype=np.uint64)

    def timed(client):
        h = client.get_hyper_log_log("bench:fault")
        m = client.get_map("bench:faultm")
        best = float("inf")
        for _ in range(reps):
            pend = []
            t0 = time.perf_counter()
            for i in range(rounds):
                pend.append(h.add_ints_async(ints[i]))
                pend.append(m.put_async(f"f{i}", i))
                if len(pend) >= 8:
                    for f in pend:
                        f.result(timeout=60)
                    pend.clear()
            for f in pend:
                f.result(timeout=60)
            best = min(best, time.perf_counter() - t0)
        return best

    base_client = RedissonTPU.create()
    try:
        timed(base_client)  # warm compile/caches
        base = timed(base_client)
    finally:
        base_client.shutdown()

    cfg = Config()
    fc = cfg.use_faults()
    fc.watchdog = True
    wired_client = RedissonTPU.create(cfg)
    try:
        timed(wired_client)
        wired = timed(wired_client)
    finally:
        wired_client.shutdown()
    pct = 100.0 * (wired / base - 1.0)
    print(f"# fault_overhead: {base * 1e3:.1f} ms bare -> {wired * 1e3:.1f} ms"
          f" with fault subsystem idle ({pct:+.2f}%)", file=sys.stderr)

    # One rebuild, timed by the coordinator itself: persist a workload,
    # inject a device-loss at d2h, wait for the heal.
    root = tempfile.mkdtemp(prefix="rtpu-bench-fault-")
    rebuild_s = 0.0
    try:
        cfg = Config()
        cfg.use_persist(root).fsync = "always"
        sc = cfg.use_serve()
        sc.retry_interval_ms = 5
        fc = cfg.use_faults()
        fc.plan = [{"seam": "d2h_complete", "fault": "device_lost",
                    "nth": rounds // 2, "kind": "hll_add"}]
        c = RedissonTPU.create(cfg)
        try:
            h = c.get_hyper_log_log("bench:fault")
            for i in range(rounds):
                try:
                    h.add_ints(ints[i])
                except Exception:  # noqa: BLE001 - the injected fault
                    pass
            if not c.fault.rebuild.wait_idle(timeout=120):
                raise RuntimeError("rebuild did not settle")
            snap = c.fault.rebuild.snapshot()
            if snap["rebuild_failures"] or not snap["rebuilt_total"]:
                raise RuntimeError(f"rebuild failed: {snap}")
            rebuild_s = snap["last_rebuild_s"]
            print(f"# fault_rebuild: {rebuild_s * 1e3:.1f} ms to re-"
                  f"materialize {snap['rebuilt_total']} target(s), "
                  f"{snap['replayed_total']} journal records replayed",
                  file=sys.stderr)
        finally:
            c.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return pct, rebuild_s


def bench_pfmerge(jax, dev, sketches=1000):
    """PFMERGE+count across 1K sketches (BASELINE: <50 ms)."""
    from redisson_tpu import engine
    from redisson_tpu.ops import hll

    stack = jax.device_put(
        np.random.default_rng(1).integers(
            0, 52, size=(sketches, hll.M), dtype=np.int32),
        dev,
    )
    merged = engine.hll_count_merged(stack)  # compile
    merged.block_until_ready()
    merge_ms = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(10):
            merged = engine.hll_count_merged(stack)
        merged.block_until_ready()
        merge_ms = min(merge_ms, (time.perf_counter() - t0) / 10 * 1e3)
    print(f"# pfmerge({sketches} sketches)+count: {merge_ms:.2f} ms",
          file=sys.stderr)
    return merge_ms


def bench_mesh(platform, n, reps, roofline=0.0, sketches=1000, quick=False):
    """Mesh data plane (PR 19): N logical shards on ONE engine stack.

    Reports the pod-scale numbers the stacks-vs-mesh tradeoff turns on:

      * mesh_inserts_per_sec — client-path HLL ingest through the mesh
        cluster facade (slot guard + shared dispatcher + sharded bank).
      * launches_per_window — observed launch count per multi-shard tape
        window (acceptance: 1.0 — one fused launch retires ALL shards'
        ops; the stacks plane pays one launch train per shard).
      * cross_shard_pfmerge_ms — PFMERGE over `sketches` HLLs whose slots
        span every shard, retired by the shard_map/pmax collective (no
        host register export).
      * pct_of_roofline — mesh ingest rate against the tape megakernel's
        roofline measured by bench_roofline on the active device. In a
        JAX_PLATFORMS=cpu rehearsal this is a proxy (CPU scatter bound,
        not TPU HBM), flagged by the `platform` tag.
    """
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_cluster(num_shards=4, data_plane="mesh")
    client = RedissonTPU.create(cfg)
    try:
        backend = client.cluster.mesh_client._routing.sketch
        rng = np.random.default_rng(23)

        # -- ingest rate + launches/window over multi-shard windows --------
        hs = [client.get_hyper_log_log(f"bench:mesh:h{i}") for i in range(4)]

        def burst():
            futs = [h.add_ints_async(
                rng.integers(0, 2**63, n // 4, dtype=np.uint64))
                for h in hs]
            for fu in futs:
                fu.result(timeout=120)

        burst()  # warmup: compile the window shapes
        s0 = backend.ingest_stats()
        t0 = time.perf_counter()
        for _ in range(max(reps - 1, 1)):
            burst()
        dt = time.perf_counter() - t0
        s1 = backend.ingest_stats()
        rate = max(reps - 1, 1) * n / dt
        windows = s1.get("tape_runs", 0) - s0.get("tape_runs", 0)
        launches = (s1.get("window_launches", 0)
                    - s0.get("window_launches", 0))
        lpw = round(launches / windows, 2) if windows else 0.0

        # -- cross-shard PFMERGE over `sketches` HLLs ----------------------
        names = [f"bench:mesh:pf{i}" for i in range(sketches)]
        futs = []
        for name in names:
            futs.append(client.get_hyper_log_log(name).add_ints_async(
                rng.integers(0, 2**63, 64, dtype=np.uint64)))
        for fu in futs:
            fu.result(timeout=300)
        tgt = client.get_hyper_log_log("bench:mesh:{pfdst}:t")
        tgt.merge_with(*names)  # compile + warm the collective
        merge_ms = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            tgt.merge_with(*names)
            merge_ms = min(merge_ms, (time.perf_counter() - t0) * 1e3)

        pct = 100.0 * rate / roofline if roofline else 0.0
        proxy = " (CPU proxy roofline)" if platform != "tpu" else ""
        print(
            f"# mesh[{platform}]: {rate/1e6:.2f} M inserts/s, "
            f"{lpw} launches/window over {windows} windows, "
            f"cross-shard pfmerge({sketches}) {merge_ms:.2f} ms, "
            f"{pct:.0f}% of roofline{proxy}",
            file=sys.stderr,
        )
        return {
            "mesh_inserts_per_sec": round(rate, 1),
            "launches_per_window": lpw,
            "cross_shard_pfmerge_ms": round(merge_ms, 3),
            "pct_of_roofline": round(pct, 1),
            "platform": platform,
            "collective_merges": backend.counters["collective_merges"],
            "multi_shard_windows": backend.counters["multi_shard_windows"],
        }
    finally:
        client.shutdown()


def bench_replica(quick=False):
    """Read-replica fleet numbers (PR 13): reads/s with 0 vs 2 replicas
    on the compute-read workload (BITCOUNT + cache-busting trickle writer,
    the --replica-smoke scaling gate's shape), and failover_s — wall time
    from killing the primary to a promoted, writable successor."""
    import os
    import shutil
    import tempfile
    import threading

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config

    n_bits = 1 << 20 if quick else 1 << 21
    n_targets = 2 if quick else 4
    phase_s = 1.0 if quick else 3.0
    n_threads = 4
    tmp = tempfile.mkdtemp(prefix="rtpu-bench-replica-")
    out = {}
    cfg = Config()
    cfg.use_local()
    cfg.use_serve()
    cfg.use_persist(os.path.join(tmp, "p")).fsync = "always"
    rc = cfg.use_replicas(2)
    rc.poll_interval_s = 0.002
    rc.max_lag_seqs = 1 << 30
    rc.health_interval_s = 0.0
    c = RedissonTPU.create(cfg)
    try:
        router = c._dispatch
        fleet = list(c.replicas.replicas)
        targets = [f"rb{i}" for i in range(n_targets)]
        for t in targets:
            c.get_bit_set(t).set_range(0, n_bits, True)
        c.wait_for_replicas(2, timeout_s=60.0)

        def warmup():
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
            stop_w, stop_r = threading.Event(), threading.Event()
            counts = [0] * n_threads

            def trickle():
                i = 0
                while not stop_w.wait(0.001):
                    c.get_bit_set(targets[i % n_targets]).set_bits(
                        [i % n_bits])
                    i += 1

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

        router.set_replicas([])
        rps0 = measure()
        router.set_replicas(fleet)
        rps2 = measure()
        out["reads_per_sec_0_replicas"] = round(rps0, 1)
        out["reads_per_sec_2_replicas"] = round(rps2, 1)
        out["read_scaling_x"] = round(rps2 / rps0, 2) if rps0 else 0.0

        # failover: kill the primary, promote, first write on the successor
        mgr = c.replicas
        c._executor.shutdown(wait=False)
        t0 = time.perf_counter()
        promoted = mgr.failover("bench kill")
        c.get_bucket("post-failover").set(1)
        out["failover_s"] = round(time.perf_counter() - t0, 4)
        out["failover_promote_s"] = round(mgr.last_failover_s, 4)
        out["resyncs_full"] = mgr.full_resyncs()
        out["resyncs_partial"] = mgr.partial_resyncs()
        assert promoted is not None
    finally:
        c.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"# replica: {out['reads_per_sec_0_replicas']:,.0f} reads/s bare "
          f"-> {out['reads_per_sec_2_replicas']:,.0f} with 2 replicas "
          f"({out['read_scaling_x']}x); failover {out['failover_s'] * 1e3:.0f}"
          f" ms to first write on the successor", file=sys.stderr)
    return out


def bench_geo(quick=False):
    """Active-active geo numbers (PR 18): geo_convergence_p99_s — wall
    time from an acked semilattice write batch at site A to its delivery
    and retirement at site B (version-vector catch-up + every dispatched
    remote apply done), and geo_link_bytes_per_op — folded/sparse wire
    bytes per shipped journal record, against the raw payload bytes the
    journal itself carries for the same records."""
    import os
    import shutil
    import tempfile

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.geo import connect_sites, converge

    rounds = 20 if quick else 60
    batch = 256 if quick else 2048
    tmp = tempfile.mkdtemp(prefix="rtpu-bench-geo-")
    out = {}

    def site(sid):
        cfg = Config()
        cfg.use_local()
        cfg.use_persist(os.path.join(tmp, sid)).fsync = "always"
        g = cfg.use_geo(sid)
        g.poll_interval_s = 0.002
        g.anti_entropy_interval_s = 0.2
        return RedissonTPU.create(cfg)

    a, b = site("A"), site("B")
    try:
        connect_sites([a, b])
        hll = a.get_hyper_log_log("geo:h")
        bits = a.get_bit_set("geo:bits")
        hll.add_all([f"warm{i}" for i in range(batch)])
        assert converge([a, b], 60), "geo bench mesh never settled"
        lat = []
        applier_b = b.geo.applier
        for r in range(rounds):
            hll.add_all([f"r{r}:{i}" for i in range(batch)])
            bits.set_bits(range(r, batch, rounds))
            head = a.geo.journal_last_seq()
            t0 = time.perf_counter()
            while (applier_b.vv.get("A", 0) < head or applier_b.pending()):
                time.sleep(0.0005)
            lat.append(time.perf_counter() - t0)
        lat.sort()
        link = a.geo.links["B"].stats
        shipped = max(link["shipped_records"], 1)
        out = {
            "geo_convergence_p50_s": round(lat[len(lat) // 2], 4),
            "geo_convergence_p99_s": round(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))], 4),
            "geo_link_bytes_per_op": round(link["link_bytes"] / shipped, 1),
            "geo_raw_bytes_per_op": round(link["raw_bytes"] / shipped, 1),
            "rounds": rounds,
            "batch_writes": batch,
        }
    finally:
        a.shutdown()
        b.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"# geo: convergence p50 {out['geo_convergence_p50_s'] * 1e3:.1f}"
          f"ms / p99 {out['geo_convergence_p99_s'] * 1e3:.1f}ms; "
          f"{out['geo_link_bytes_per_op']:,.0f}B/op on the link vs "
          f"{out['geo_raw_bytes_per_op']:,.0f}B/op raw", file=sys.stderr)
    return out


def bench_ha(quick=False):
    """Shard-level HA numbers (PR 14): cluster_failover_s — wall time
    from killing a shard's primary to the first acked write on its
    promotee — and reads_served_during_failover — replica-served reads
    that completed inside that window (the survivor fleet keeps the
    shard readable while it has no primary)."""
    import os
    import shutil
    import tempfile
    import threading

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.ops.crc16 import key_slot

    n_readers = 2
    tmp = tempfile.mkdtemp(prefix="rtpu-bench-ha-")
    out = {}
    cfg = Config()
    cfg.use_cluster(num_shards=2, dir=os.path.join(tmp, "cl"),
                    replicas_per_shard=2)
    rc = cfg.use_replicas(2)
    rc.poll_interval_s = 0.002
    rc.max_lag_seqs = 1 << 30
    rc.health_interval_s = 0.0
    c = RedissonTPU.create(cfg)
    try:
        mgr = c.cluster
        table = mgr.router.slot_table()
        keys = [f"hb{i}" for i in range(400)
                if table[key_slot(f"hb{i}")] == 0][:8]
        for k in keys:
            c.get_bucket(k).set("v0")
        s0 = mgr.shards[0]
        fleet = s0.replicas
        deadline = time.monotonic() + 30
        while (any(r.lag() > 0 for r in fleet.replicas)
               and time.monotonic() < deadline):
            time.sleep(0.005)

        stop = threading.Event()
        stamps = [[] for _ in range(n_readers)]

        def reader(slot):
            j = slot
            while not stop.is_set():
                try:
                    fut, rep, _ = s0.dispatch.routed_read(
                        keys[j % len(keys)], "get", None,
                        max_lag=1 << 30, read_your_writes=False)
                    fut.result(30)
                    if rep is not None:  # replica-served, not primary
                        stamps[slot].append(time.perf_counter())
                except Exception:  # noqa: BLE001 — reads racing the kill may fail; only successes count
                    pass
                j += 1

        threads = [threading.Thread(target=reader, args=(s,), daemon=True)
                   for s in range(n_readers)]
        for t in threads:
            t.start()
        time.sleep(0.2 if quick else 0.5)
        t_kill = time.perf_counter()
        s0.client._executor.shutdown(wait=False)  # shard primary dies
        promoted = fleet.failover("bench kill")
        c.get_bucket(keys[0]).set("post-failover")  # first write lands
        t_done = time.perf_counter()
        stop.set()
        for t in threads:
            t.join(30)
        out["cluster_failover_s"] = round(t_done - t_kill, 4)
        out["cluster_failover_promote_s"] = round(fleet.last_failover_s, 4)
        out["reads_served_during_failover"] = sum(
            1 for ts in stamps for ts_i in ts if t_kill <= ts_i <= t_done)
        assert promoted is not None
    finally:
        c.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"# ha: shard failover {out['cluster_failover_s'] * 1e3:.0f} ms "
          f"to first write on the promotee; "
          f"{out['reads_served_during_failover']} replica reads served "
          f"while the shard had no primary", file=sys.stderr)
    return out


def bench_wire(quick=False):
    """RESP wire front-end (PR 16): pipelined command throughput over a
    real TCP socket, single-command round-trip p99, and the connection
    scheduler's achieved coalescing depth (engine ops per execute_many
    window — the wire analogue of the pipeline overlap ratio). Also
    force-arms the loop-stall witness (PR 17) so BENCH json carries
    loop_lag_p99_us next to wire_rtt_p99_us: tail latency attributable
    to loop stalls vs engine time."""
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.interop.resp_client import SyncRespClient
    from redisson_tpu.loopwitness import loop_gauges, uninstall, watch_loop

    n_cmds = 2_000 if quick else 20_000
    depth = 64
    pings = 200 if quick else 1_000

    cfg = Config()
    cfg.use_serve()
    cfg.use_wire()
    c = RedissonTPU(cfg)
    out = {}
    try:
        watch_loop(c.wire._loop, "bench-wire", force=True)
        cli = SyncRespClient("127.0.0.1", c.wire.port,
                             retry_attempts=1, timeout=30.0)
        cli.connect()
        try:
            # Round-trip latency: serial PINGs, one in flight at a time.
            lat = []
            for _ in range(pings):
                t0 = time.perf_counter()
                cli.execute("PING")
                lat.append(time.perf_counter() - t0)
            lat.sort()
            out["wire_rtt_p99_us"] = round(
                lat[int(0.99 * (len(lat) - 1))] * 1e6, 1)

            # Pipelined throughput: engine commands at fixed client depth.
            sent = 0
            t0 = time.perf_counter()
            while sent < n_cmds:
                cmds = [("SETBIT", "bw:bits", str(sent + j), "1")
                        for j in range(depth)]
                cli.pipeline(cmds)
                sent += depth
            wall = time.perf_counter() - t0
            out["wire_ops_per_sec"] = round(sent / wall, 1)
            out["wire_pipeline_depth"] = round(
                c.wire.snapshot()["avg_window_depth"], 2)
            out["loop_lag_p99_us"] = loop_gauges(
                c.wire._loop)["loop_lag_p99_us"]
        finally:
            cli.close()
    finally:
        c.shutdown()
        uninstall()  # restore Handle._run for the rest of the bench
    print(f"# wire: {out['wire_ops_per_sec']:,.0f} pipelined ops/s, "
          f"rtt p99 {out['wire_rtt_p99_us']:.0f} us, "
          f"loop lag p99 {out['loop_lag_p99_us']} us, "
          f"window depth {out['wire_pipeline_depth']}", file=sys.stderr)
    return out


def main():
    quick = "--quick" in sys.argv[1:]

    from redisson_tpu.tpu_boot import acquire_devices, device_stamp

    # No TPU is an error; JAX_PLATFORMS=cpu is a rehearsal, stamped cpu.
    devices = acquire_devices()
    import jax

    dev = devices[0]
    stamp = device_stamp(devices)
    platform = stamp["platform"]

    n = 1 << 14 if quick else 1 << 20
    reps = 4 if quick else 32
    result = {
        "metric": "hll_inserts_per_sec_per_chip",
        "value": 0.0,
        "unit": "inserts/s",
        "vs_baseline": 0.0,
        "platform": platform,
        "device_kind": stamp["kind"],
        "device_count": stamp["count"],
    }
    try:
        kernel = bench_kernel(jax, dev, n, reps)
        result["kernel_inserts_per_sec"] = round(kernel["scatter"], 1)
        result["kernel_sort_inserts_per_sec"] = round(kernel["sort"], 1)
        result["kernel_segment_inserts_per_sec"] = round(kernel["segment"], 1)
    except Exception as exc:  # noqa: BLE001
        print(f"# kernel bench failed: {exc!r}", file=sys.stderr)
    try:
        result.update(bench_roofline(
            jax, dev, n, result.get("kernel_inserts_per_sec", 0.0),
            segment_rate=result.get("kernel_segment_inserts_per_sec", 0.0),
            quick=quick))
    except Exception as exc:  # noqa: BLE001
        print(f"# roofline bench failed: {exc!r}", file=sys.stderr)
    try:
        result["host_budget"] = bench_host_budget(jax, dev, n)
    except Exception as exc:  # noqa: BLE001
        print(f"# host budget bench failed: {exc!r}", file=sys.stderr)
    try:
        e2e, err, op_pcts = bench_end_to_end(n, reps)
        result["hostfold_inserts_per_sec"] = round(e2e, 1)
        result["cardinality_rel_err"] = round(err, 5)
        if op_pcts:
            result["hll_add_latency_us"] = op_pcts
        if INGEST_CHOICE:
            result["ingest"] = dict(INGEST_CHOICE)
    except Exception as exc:  # noqa: BLE001
        print(f"# end-to-end bench failed: {exc!r}", file=sys.stderr)
    try:
        result["device_ingest_inserts_per_sec"] = round(
            bench_device_ingest(jax, dev, n, reps), 1)
    except Exception as exc:  # noqa: BLE001
        print(f"# device ingest bench failed: {exc!r}", file=sys.stderr)
    try:
        from redisson_tpu import native as _native

        if _native.available():
            result["delta"] = bench_delta_ingest(n, reps)
        else:
            print("# delta ingest bench skipped: native lib unavailable",
                  file=sys.stderr)
    except Exception as exc:  # noqa: BLE001
        print(f"# delta ingest bench failed: {exc!r}", file=sys.stderr)
    try:
        from redisson_tpu import native as _native

        if _native.available():
            result["tape_window"] = bench_tape_window(
                1 << 12 if quick else 1 << 16, 3 if quick else 12)
        else:
            print("# tape window bench skipped: native lib unavailable",
                  file=sys.stderr)
    except Exception as exc:  # noqa: BLE001
        print(f"# tape window bench failed: {exc!r}", file=sys.stderr)
    try:
        result["hll_count_cached"] = bench_read_cache(
            1 << 12 if quick else 1 << 18, reps=5 if quick else 20)
    except Exception as exc:  # noqa: BLE001
        print(f"# read-cache bench failed: {exc!r}", file=sys.stderr)
    try:
        result["journal_overhead_pct"] = round(bench_journal_overhead(
            50 if quick else 200, reps=2 if quick else 3), 1)
    except Exception as exc:  # noqa: BLE001
        print(f"# journal overhead bench failed: {exc!r}", file=sys.stderr)
    try:
        result["lock_witness_overhead_pct"] = round(bench_lock_witness(
            50 if quick else 200, reps=2 if quick else 3), 1)
    except Exception as exc:  # noqa: BLE001
        print(f"# lock witness bench failed: {exc!r}", file=sys.stderr)
    try:
        result["contract_witness_overhead_pct"] = round(
            bench_contract_witness(50 if quick else 200,
                                   reps=2 if quick else 3), 1)
    except Exception as exc:  # noqa: BLE001
        print(f"# contract witness bench failed: {exc!r}", file=sys.stderr)
    try:
        pct, rebuild_s = bench_fault(
            50 if quick else 200, reps=2 if quick else 3)
        result["fault_overhead_pct"] = round(pct, 2)
        result["fault_rebuild_s"] = round(rebuild_s, 4)
    except Exception as exc:  # noqa: BLE001
        print(f"# fault bench failed: {exc!r}", file=sys.stderr)
    try:
        result["pfmerge_1000_ms"] = round(
            bench_pfmerge(jax, dev, 32 if quick else 1000), 3)
    except Exception as exc:  # noqa: BLE001
        print(f"# pfmerge bench failed: {exc!r}", file=sys.stderr)
    try:
        result["mesh"] = bench_mesh(
            platform, 1 << 12 if quick else 1 << 16, 3 if quick else 12,
            roofline=result.get("roofline_inserts_per_sec", 0.0),
            sketches=32 if quick else 1000, quick=quick)
    except Exception as exc:  # noqa: BLE001
        print(f"# mesh bench failed: {exc!r}", file=sys.stderr)
    try:
        result.update(bench_wire(quick))
    except Exception as exc:  # noqa: BLE001
        print(f"# wire bench failed: {exc!r}", file=sys.stderr)
    try:
        result["replica"] = bench_replica(quick)
    except Exception as exc:  # noqa: BLE001
        print(f"# replica bench failed: {exc!r}", file=sys.stderr)
    try:
        result["ha"] = bench_ha(quick)
    except Exception as exc:  # noqa: BLE001
        print(f"# ha bench failed: {exc!r}", file=sys.stderr)
    try:
        result["geo"] = bench_geo(quick)
    except Exception as exc:  # noqa: BLE001
        print(f"# geo bench failed: {exc!r}", file=sys.stderr)
    try:
        mem = bench_memstat(1 << 12 if quick else 1 << 18)
        result["hbm_live_bytes"] = mem["hbm_live_bytes"]
        result["hbm_scratch_bytes"] = mem["hbm_scratch_bytes"]
        result["bytes_per_key"] = mem["bytes_per_key"]
        result["memstat"] = mem
    except Exception as exc:  # noqa: BLE001
        print(f"# memstat bench failed: {exc!r}", file=sys.stderr)
    try:
        from redisson_tpu.ingest.planner import default_planner

        table = default_planner().table()
        if table:
            result["ingest_cost_table_ns_per_key"] = {
                k: {p: round(v, 2) for p, v in costs.items()}
                for k, costs in table.items()}
    except Exception as exc:  # noqa: BLE001
        print(f"# planner table dump failed: {exc!r}", file=sys.stderr)
    # HEADLINE = the chip: device-resident client-path ingest (VERDICT r3
    # weak #2 — the hostfold rate conflates host silicon with the TPU; it
    # stays reported as the link-starved adaptive path). Fallbacks keep a
    # device number on transient failures: raw kernel rate, then hostfold.
    result["value"] = (
        result.get("device_ingest_inserts_per_sec")
        or result.get("kernel_inserts_per_sec")
        or result.get("hostfold_inserts_per_sec", 0.0))
    result["value_is"] = (
        "device_ingest" if result.get("device_ingest_inserts_per_sec")
        else "kernel" if result.get("kernel_inserts_per_sec")
        else "hostfold")
    result["vs_baseline"] = round(result["value"] / 100e6, 4)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
