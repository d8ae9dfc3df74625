#!/usr/bin/env python
"""Drive the served sketch path once on the chip and check its answers.

    python chip_smoke.py             one chip: phases a-e at BASELINE sizes
    python chip_smoke.py --chips 4   four chips: the mesh data plane against
                                     the stacks plane, and nothing else

Every phase runs in this one process (the wire server runs on a thread of
it); the only child process is the native build (`make -C native`). Each
phase checks what comes out against a plain numpy reference or against
another path of the system, and any failure ends the run non-zero. Without
a TPU the run stops before any phase. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports
the device. Times, compile counts and peak bytes printed on the way are
informational, not claims.

The phases are functions of their sizes, so tests/test_chip_smoke.py runs
them at tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

# ---------------------------------------------------------------------------
# Plain references: murmur3_x64_128 of 8-byte little-endian keys and the HLL
# register fold, in numpy, independent of the code under test.

_C1 = np.uint64(0x87C37B91114253D5)
_C2 = np.uint64(0x4CF5AD432745937F)
HLL_P = 14
HLL_M = 1 << HLL_P


def _rotl(x, r: int):
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def _fmix(k):
    k = k ^ (k >> np.uint64(33))
    k = k * np.uint64(0xFF51AFD7ED558CCD)
    k = k ^ (k >> np.uint64(33))
    k = k * np.uint64(0xC4CEB9FE1A85EC53)
    return k ^ (k >> np.uint64(33))


def murmur3_u64(keys: np.ndarray, seed: int = 0):
    """(h1, h2) of murmur3_x64_128 over each key's 8 little-endian bytes."""
    keys = np.asarray(keys, np.uint64)
    with np.errstate(over="ignore"):
        h1 = np.full(keys.shape, seed, np.uint64)
        h2 = h1.copy()
        k1 = _rotl(keys * _C1, 31) * _C2  # the 8-byte tail
        h1 = h1 ^ k1
        h1 = h1 ^ np.uint64(8)
        h2 = h2 ^ np.uint64(8)
        h1 = h1 + h2
        h2 = h2 + h1
        h1 = _fmix(h1)
        h2 = _fmix(h2)
        h1 = h1 + h2
        h2 = h2 + h1
    return h1, h2


def hll_registers(keys: np.ndarray, seed: int = 0) -> np.ndarray:
    """Dense p=14 HLL registers of `keys` (Redis hllPatLen: bucket = low
    14 bits, rank = trailing zeros of the rest, capped at 51)."""
    h1, _ = murmur3_u64(keys, seed)
    bucket = (h1 & np.uint64(HLL_M - 1)).astype(np.int64)
    rest = (h1 >> np.uint64(HLL_P)) | np.uint64(1 << (64 - HLL_P))
    low = rest & (~rest + np.uint64(1))  # lowest set bit, a power of two
    rank = np.log2(low.astype(np.float64)).astype(np.int64) + 1
    regs = np.zeros(HLL_M, np.int64)
    np.maximum.at(regs, bucket, rank)
    return regs.astype(np.uint8)


def distinct_keys(n: int, seed: int, offset: int = 0) -> np.ndarray:
    """n distinct u64 keys: an odd-multiplier affine map of [offset,
    offset + n), a bijection mod 2^64, so disjoint offsets never collide."""
    rng = np.random.default_rng(seed)
    mult = np.uint64(int(rng.integers(1, 2**63)) * 2 + 1)
    add = np.uint64(int(rng.integers(0, 2**63)))
    with np.errstate(over="ignore"):
        return (np.arange(offset, offset + n, dtype=np.uint64) * mult) + add


def bloom_params(m: int, k: int):
    """(expected_insertions, false_probability) that the reference sizing
    (optimalNumOfBits / optimalNumOfHashFunctions) turns into exactly m
    bits and k hashes."""
    n = max(1, round(m * math.log(2.0) / k))
    p = math.exp(-(m + 0.5) * math.log(2.0) ** 2 / n)
    return n, p


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Facade helpers


def _client(ingest: str = "auto", wire: bool = False):
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config, TpuConfig

    cfg = Config(tpu=TpuConfig(ingest=ingest))
    if wire:
        cfg.use_wire()
    return RedissonTPU.create(cfg)


def _hll_regs(c, name: str) -> np.ndarray:
    return np.asarray(c._executor.execute_sync(name, "hll_export", None)[0])


def _cells(c, name: str) -> np.ndarray:
    return np.asarray(c._executor.execute_sync(name, "bits_export", None)[1])


def _add_in_parts(obj, keys, parts: int = 4):
    futs = [obj.add_ints_async(p) for p in np.array_split(keys, parts)]
    for f in futs:
        f.result()


# ---------------------------------------------------------------------------
# Phases

#: Ingest values that reach a distinct device path.
INGESTS = ("auto", "device", "segment", "hostfold", "delta")


def phase_hll(n_keys: int = 1 << 20, ingests=INGESTS, seed: int = 0):
    """a) n distinct keys into one HLL through the facade under each
    ingest path: identical registers, equal to the numpy fold, and an
    estimate within 2%."""
    keys = distinct_keys(n_keys, seed)
    want = hll_registers(keys)
    out = {}
    for ingest in ingests:
        c = _client(ingest)
        try:
            h = c.get_hyper_log_log("smoke:hll")
            _add_in_parts(h, keys)
            est = h.count()
            regs = _hll_regs(c, "smoke:hll")
        finally:
            c.shutdown()
        check(np.array_equal(regs, want),
              f"ingest={ingest}: registers differ from the numpy fold in "
              f"{int(np.sum(regs != want))} of {HLL_M} buckets")
        err = abs(est - n_keys) / n_keys
        check(err < 0.02, f"ingest={ingest}: estimate {est} is {err:.4f} "
                          f"off {n_keys}")
        out[ingest] = {"estimate": int(est), "error": err}
    return out


def phase_merge(n_sketches: int = 256, per_sketch: int = 40_000,
                seed: int = 1):
    """b) RBatch PFADD across n HLLs, then PFMERGE of all of them and
    PFCOUNT (BASELINE config 3), against a numpy fold of the same keys."""
    keys = distinct_keys(n_sketches * per_sketch, seed).reshape(
        n_sketches, per_sketch)
    names = [f"smoke:s{i}" for i in range(n_sketches)]
    c = _client()
    try:
        batch = c.create_batch()
        for name, ks in zip(names, keys):
            batch.get_hyper_log_log(name).add_ints_async(ks)
        batch.execute()
        dest = c.get_hyper_log_log("smoke:union")
        dest.merge_with(*names)
        est = dest.count()
        union = _hll_regs(c, "smoke:union")
        first = _hll_regs(c, names[0])
    finally:
        c.shutdown()
    check(np.array_equal(first, hll_registers(keys[0])),
          "sketch 0 differs from the numpy fold of its keys")
    check(np.array_equal(union, hll_registers(keys.reshape(-1))),
          "PFMERGE registers differ from the numpy fold of all keys")
    total = n_sketches * per_sketch
    err = abs(est - total) / total
    check(err < 0.02, f"union estimate {est} is {err:.4f} off {total}")
    return {"estimate": int(est), "error": err}


def phase_bloom(n_keys: int = 1 << 20, m: int = 1 << 28, k: int = 7,
                ingests=("auto", "delta"), seed: int = 2):
    """c) Bloom filter of m bits and k hashes (BASELINE config 2): no
    false negatives on inserted keys, a false-positive rate on fresh keys
    within theory plus 5 sigma (and 5 keys), and identical bits under
    each ingest path (delta unpacks its packed planes on the device)."""
    keys = distinct_keys(n_keys, seed)
    fresh = distinct_keys(n_keys, seed, offset=n_keys)
    expected, fpp = bloom_params(m, k)
    theory = (1.0 - math.exp(-k * n_keys / m)) ** k
    limit = theory + 5 * math.sqrt(theory * (1 - theory) / n_keys) + 5 / n_keys
    cells = {}
    out = {"theoretical_fpr": theory, "fpr_limit": limit}
    for ingest in ingests:
        c = _client(ingest)
        try:
            bf = c.get_bloom_filter("smoke:bloom")
            bf.try_init(expected_insertions=expected, false_probability=fpp)
            check(bf.get_size() == m and bf.get_hash_iterations() == k,
                  f"bloom sized {bf.get_size()}/{bf.get_hash_iterations()}, "
                  f"not {m}/{k}")
            _add_in_parts(bf, keys)
            hits = bf.contains_ints(keys)
            fp = int(np.sum(bf.contains_ints(fresh)))
            cells[ingest] = _cells(c, "smoke:bloom")
        finally:
            c.shutdown()
        check(bool(np.all(hits)),
              f"ingest={ingest}: {int(np.sum(~hits))} false negatives")
        fpr = fp / n_keys
        check(fpr <= limit, f"ingest={ingest}: false-positive rate {fpr} "
                            f"over {limit}")
        out[ingest] = {"false_positives": fp, "fpr": fpr}
    first = cells[ingests[0]]
    for ingest in ingests[1:]:
        check(np.array_equal(cells[ingest], first),
              f"bloom bits under ingest={ingest} differ from "
              f"ingest={ingests[0]}")
    return out


def phase_bits(nbits: int = 1 << 28, nset: int = 1 << 20, seed: int = 3):
    """d) three bit sets of `nbits` bits: BITCOUNT, and BITOP AND/OR/XOR
    over all three, equal to numpy bit for bit."""
    rng = np.random.default_rng(seed)
    idx = [np.append(rng.integers(0, nbits, nset), nbits - 1)
           for _ in range(3)]
    ref = []
    for ix in idx:
        cells = np.zeros(nbits, np.uint8)
        cells[ix] = 1
        ref.append(cells)
    ops = {"and": np.bitwise_and, "or": np.bitwise_or,
           "xor": np.bitwise_xor}
    out = {}
    c = _client()
    try:
        names = [f"smoke:bits{i}" for i in range(3)]
        for name, ix, cells in zip(names, idx, ref):
            bs = c.get_bit_set(name)
            bs.set_bits(ix)
            got = bs.cardinality()
            check(got == int(cells.sum()),
                  f"BITCOUNT {name}: {got} != numpy {int(cells.sum())}")
        for op, fn in ops.items():
            dest = c.get_bit_set(f"smoke:{op}")
            dest.set_bits(idx[0])
            getattr(dest, op if op == "xor" else op + "_")(*names[1:])
            want = fn(fn(ref[0], ref[1]), ref[2])
            check(np.array_equal(_cells(c, f"smoke:{op}"), want),
                  f"BITOP {op} cells differ from numpy")
            got = dest.cardinality()
            check(got == int(want.sum()),
                  f"BITCOUNT after BITOP {op}: {got} != {int(want.sum())}")
            out[op] = int(got)
    finally:
        c.shutdown()
    return out


def phase_wire(n_elems: int = 10_000, seed: int = 4):
    """e) RESP wire: PING, PFADD, PFCOUNT and PFMERGE through
    SyncRespClient, each reply equal to the facade's answer."""
    from redisson_tpu.interop.resp_client import SyncRespClient

    rng = np.random.default_rng(seed)
    a = [b"a:%d" % v for v in rng.integers(0, 2**62, n_elems)]
    b = [b"b:%d" % v for v in rng.integers(0, 2**62, n_elems)] + a[:100]
    c = _client(wire=True)
    # The first replies wait for their kernels to compile on the device.
    cli = SyncRespClient("127.0.0.1", c.wire.port, timeout=300.0,
                         retry_attempts=0)
    try:
        cli.connect()
        check(cli.execute("PING") == b"PONG", "PING")
        check(cli.execute("PFADD", "w:a", *a) == 1, "PFADD w:a")
        check(cli.execute("PFADD", "w:b", *b) == 1, "PFADD w:b")
        check(c.get_hyper_log_log("f:a").add_all(a), "facade add f:a")
        check(c.get_hyper_log_log("f:b").add_all(b), "facade add f:b")
        for w, f in (("w:a", "f:a"), ("w:b", "f:b")):
            got = cli.execute("PFCOUNT", w)
            check(got == c.get_hyper_log_log(f).count()
                  == c.get_hyper_log_log(w).count(),
                  f"PFCOUNT {w} = {got} differs from the facade")
        both = cli.execute("PFCOUNT", "w:a", "w:b")
        check(both == c.get_hyper_log_log("f:a").count_with("f:b"),
              f"PFCOUNT w:a w:b = {both} differs from the facade")
        check(cli.execute("PFMERGE", "w:m", "w:a", "w:b") == b"OK",
              "PFMERGE")
        c.get_hyper_log_log("f:m").merge_with("f:a", "f:b")
        merged = cli.execute("PFCOUNT", "w:m")
        check(merged == c.get_hyper_log_log("f:m").count() == both,
              f"PFCOUNT w:m = {merged} differs from the facade")
        check(np.array_equal(_hll_regs(c, "w:m"), _hll_regs(c, "f:m")),
              "PFMERGE registers over the wire differ from the facade's")
    finally:
        cli.close()
        c.shutdown()
    return {"pfcount_union": int(merged)}


def phase_mesh(num_shards: int = 4, n_sketches: int = 4096,
               per_sketch: int = 256, seed: int = 5):
    """--chips 4: a num_shards-shard mesh cluster (data_plane="mesh", one
    NamedSharding bank) holding n_sketches HLL keys (BASELINE config 4),
    against a stacks cluster fed the same keys: cross-shard PFMERGE,
    PFCOUNT and DBSIZE answers agree, and the bank spans every device."""
    import jax

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config, TpuConfig

    keys = distinct_keys(n_sketches * per_sketch, seed).reshape(
        n_sketches, per_sketch)
    names = [f"mesh:h{i}" for i in range(n_sketches)]
    answers = {}
    for plane in ("stacks", "mesh"):
        cfg = Config(tpu=TpuConfig())
        cfg.use_cluster(num_shards=num_shards, data_plane=plane)
        c = RedissonTPU.create(cfg)
        try:
            batch = c.create_batch()
            for name, ks in zip(names, keys):
                batch.get_hyper_log_log(name).add_ints_async(ks)
            batch.execute()
            dest = c.get_hyper_log_log("mesh:union")
            dest.merge_with(*names)
            answers[plane] = {
                "pfmerge_count": dest.count(),
                "pfcount_multi": c.get_hyper_log_log(names[0]).count_with(
                    *names[1:]),
                "dbsize": c.get_keys().count(),
                "registers": _cluster_regs(c, "mesh:union"),
            }
            if plane == "mesh":
                backend = c.cluster.mesh_client._routing.sketch
                devs = {s.device for s in backend.bank.addressable_shards}
                answers[plane]["bank_devices"] = len(devs)
                answers[plane]["occupancy"] = backend.mesh_occupancy()
                answers[plane]["collective_merges"] = backend.counters[
                    "collective_merges"]
        finally:
            c.shutdown()
    stacks, mesh = answers["stacks"], answers["mesh"]
    ndev = len(jax.devices())
    check(mesh["bank_devices"] == ndev,
          f"mesh bank spans {mesh['bank_devices']} of {ndev} devices")
    check(mesh["collective_merges"] >= 1, "no collective merge ran")
    check(np.array_equal(mesh["registers"], stacks["registers"]),
          "PFMERGE registers differ between mesh and stacks")
    check(np.array_equal(stacks["registers"], hll_registers(keys.reshape(-1))),
          "PFMERGE registers differ from the numpy fold")
    for key in ("pfmerge_count", "pfcount_multi", "dbsize"):
        check(mesh[key] == stacks[key],
              f"{key}: mesh {mesh[key]} != stacks {stacks[key]}")
    check(mesh["occupancy"] == n_sketches + 1,
          f"mesh occupancy {mesh['occupancy']} != {n_sketches + 1} HLLs")
    total = n_sketches * per_sketch
    err = abs(mesh["pfmerge_count"] - total) / total
    check(err < 0.02, f"union estimate {mesh['pfmerge_count']} is {err:.4f} "
                      f"off {total}")
    return {k: v for k, v in mesh.items() if k != "registers"} | {
        "error": err}


def _cluster_regs(c, name: str) -> np.ndarray:
    return np.asarray(
        c.cluster.router.execute_sync(name, "hll_export", None)[0])


ONE_CHIP = (("a_hll", phase_hll), ("b_merge", phase_merge),
            ("c_bloom", phase_bloom), ("d_bits", phase_bits),
            ("e_wire", phase_wire))
FOUR_CHIPS = (("mesh_vs_stacks", phase_mesh),)


# ---------------------------------------------------------------------------
# Driver


class _CompileCounter:
    """Counts XLA backend compiles through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1


def _peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return None if None in peaks else max(peaks)


def run_phases(phases, devices, log=sys.stderr) -> dict:
    """Run each phase once; the first failure raises."""
    compiles = _CompileCounter()
    results = {}
    for name, fn in phases:
        c0, t0 = compiles.n, time.perf_counter()
        results[name] = fn()
        wall = time.perf_counter() - t0
        print(f"# phase {name}: wall {wall:.3f} s, compiles "
              f"{compiles.n - c0}, peak device bytes "
              f"{_peak_bytes(devices)}, result {results[name]}", file=log)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    repo = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(repo, "redisson_tpu")):
        print("chip_smoke: run it from a checkout of the repo",
              file=sys.stderr)
        return 2
    sys.path.insert(0, repo)
    from redisson_tpu import native, tpu_boot

    try:
        devices = tpu_boot.acquire_devices(allow_cpu=False)
    except RuntimeError as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX gives {len(devices)}", file=sys.stderr)
        return 1
    native_ok = native.available()
    print(f"# native.AVAILABLE = {native.AVAILABLE}", file=sys.stderr)
    if not native_ok:
        print("chip_smoke: the native library did not build",
              file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    try:
        run_phases(ONE_CHIP if args.chips == 1 else FOUR_CHIPS, devices)
    except Exception:  # noqa: BLE001 — any phase failure ends the run non-zero
        traceback.print_exc()
        return 1
    print(f"# all phases passed in {time.perf_counter() - t0:.3f} s",
          file=sys.stderr)
    print(json.dumps({"ok": True, "device": tpu_boot.device_stamp(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
