"""The TPU sketch backend: executes op runs against the SketchStore.

This is the component the north star swaps in behind the executor seam —
where the reference encodes RESP and awaits a Redis reply
(`client/handler/CommandEncoder.java` / `CommandDecoder.java`), this backend
pads the coalesced key batch to a bucket, invokes one fused jitted kernel
(redisson_tpu.engine), swaps the new state into the store, and completes the
op futures.
"""

from __future__ import annotations

import bisect
import functools
import queue
import threading
import time
from collections import OrderedDict
from typing import Callable, List, Optional

import jax.numpy as jnp
import numpy as np

from redisson_tpu import engine
from redisson_tpu.executor import Op

# graftlint Tier C guarded-by audit: the backend runs entirely on the
# executor's dispatcher thread — run() and the allocator grow hook are
# invoked from inside backend.run.
GUARDED_BY = {
    "TpuBackend.bank":
        "thread:dispatcher-confined — every writer (_ensure_bank, "
        "_grow_bank via RowAllocator, _hll_row) runs "
        "inside backend.run on the dispatcher; checkpoint load replaces "
        "it only through an executor barrier",
}
from redisson_tpu.fault import inject as fault_inject
from redisson_tpu.fault.taxonomy import classify
from redisson_tpu.ingest import delta as delta_mod
from redisson_tpu.ingest.pipeline import StagingPipeline
from redisson_tpu.ops import bitset as bitset_ops, bloom as bloom_ops
from redisson_tpu.ops import bloom_math
from redisson_tpu.ops import hll as hll_ops
from redisson_tpu.store import ObjectType, SketchStore, WrongTypeError
from redisson_tpu.trace.stages import stage


class Completer:
    """Resolves op futures off the dispatcher thread.

    jax dispatch is asynchronous: a kernel call returns device Arrays
    immediately and materializing any of them (`bool(changed)`,
    `np.asarray(old)`) blocks until the device catches up. Round 2 did that
    materialization on the dispatcher thread per chunk, serializing
    dispatch→wait→dispatch and capping the client path at ~6 M inserts/s
    (VERDICT r2 weak #1). Here the dispatcher only *dispatches* — each run's
    device results are handed to this single FIFO thread, which blocks on
    them and completes the futures, preserving per-object completion order.
    (The reference's analogue: promises complete on netty event-loop
    threads, never the submitting thread, `CommandDecoder.java:340-355`.)

    The queue is bounded so a free-running producer cannot pile up unbounded
    in-flight device work/host buffers (the dispatcher blocks on put() once
    `maxsize` completions are pending — soft backpressure).

    A completion is a callable that materializes its run's device results
    (the blocking D2H) and returns the callable that resolves the futures,
    or None once it has resolved them itself (a failed materialization).
    The two halves run under the `completer.d2h` / `completer.resolve`
    stage spans and add their perf_counter ns to `counters`
    (`d2h_wait_ns`, `resolve_ns`, `runs_materialized`: completions run).
    """

    def __init__(self, maxsize: int = 64, counters: Optional[dict] = None):
        self.counters = {} if counters is None else counters
        for key in ("d2h_wait_ns", "resolve_ns", "runs_materialized"):
            self.counters.setdefault(key, 0)
        self._q: "queue.Queue[Callable]" = queue.Queue(maxsize=maxsize)
        self._thread = threading.Thread(
            target=self._loop, name="redisson-tpu-completer", daemon=True
        )
        self._thread.start()

    def submit(self, fn: Callable[[], None]) -> None:
        self._q.put(fn)

    def _loop(self):
        counters = self.counters
        while True:
            fn = self._q.get()
            if fn is None:
                return
            t0 = time.perf_counter_ns()
            t1 = None
            try:
                with stage("completer.d2h"):
                    resolve = fn()
                t1 = time.perf_counter_ns()
                if resolve is not None:
                    with stage("completer.resolve"):
                        resolve()
            except Exception:
                # graftlint: allow-bare(completion closures own their futures and classify internally; an escape here means the futures are already resolved — re-raising would only kill the completer thread)
                pass
            finally:
                t2 = time.perf_counter_ns()
                t1 = t2 if t1 is None else t1
                counters["d2h_wait_ns"] += t1 - t0
                counters["resolve_ns"] += t2 - t1
                counters["runs_materialized"] += 1
                self._q.task_done()

    def drain(self):
        """Block until every submitted completion has run."""
        self._q.join()

    def shutdown(self):
        self.drain()
        self._q.put(None)


def _format_runs(ops: List[Op]):
    """Split a coalesced run into consecutive same-format groups (packed vs
    bytes), preserving op order so positional result slicing stays valid."""
    runs: List = []
    for op in ops:
        fmt = "packed" if "packed" in op.payload else "bytes"
        if runs and runs[-1][0] == fmt:
            runs[-1][1].append(op)
        else:
            runs.append((fmt, [op]))
    return runs


def _segments(arrays: List[np.ndarray], small: int) -> List[np.ndarray]:
    """Group row arrays for dispatch: runs of small arrays concatenate into
    one bucket-bound buffer (amortizing per-call overhead), large arrays
    pass through untouched (avoiding an 8 B/key memcpy on the dispatcher)."""
    out, pending, pending_rows = [], [], 0
    for a in arrays:
        if a.shape[0] >= small:
            if pending:
                out.append(np.concatenate(pending))
                pending, pending_rows = [], 0
            out.append(a)
        else:
            pending.append(a)
            pending_rows += a.shape[0]
            if pending_rows >= small:
                out.append(np.concatenate(pending))
                pending, pending_rows = [], 0
    if pending:
        out.append(pending[0] if len(pending) == 1 else np.concatenate(pending))
    return out


def _start_d2h(x):
    """Kick off an async device->host copy so the completer's later
    materialization finds the bytes already in flight (one blocking
    readback costs a full round trip; overlapping them is the difference
    between per-run and per-round-trip throughput)."""
    start = getattr(x, "copy_to_host_async", None)
    if start is not None:
        try:
            start()
        except Exception:  # pragma: no cover — committed arrays only
            # graftlint: allow-bare(best-effort copy kickoff: the completer's materialization retries the same readback and classifies its failure)
            pass
    return x


def _trace_cache(ops, hit: bool) -> None:
    """Stamp read-cache hit/miss on any sampled trace spans riding `ops`
    (executor.Op.span; replayed/synthetic ops may lack the attribute)."""
    name = "cache_hit" if hit else "cache_miss"
    for op in ops:
        span = getattr(op, "span", None)
        if span is not None and span.t1 is None:
            span.event(name)
            span.annotations.setdefault("read_cache", "hit" if hit else "miss")


def _complete_all(ops: List[Op], materialize: Callable[[], object]) -> Callable:
    """Completion (see Completer) giving every op materialize()'s value
    (or error)."""

    def run():
        try:
            fault_inject.fire("d2h_complete",
                              kind=ops[0].kind if ops else "",
                              target=ops[0].target if ops else "")
            value = materialize()
        except Exception as exc:  # noqa: BLE001 — device errors surface here
            # Post-dispatch failure: the device run already launched, so a
            # transient error here means the commit state is unknown —
            # classify maps it to StateUncertainFault and the executor's
            # fault listener routes the targets to the rebuild path.
            _fail_ops(ops, classify(exc, seam="d2h_complete"))
            return None

        def resolve():
            for op in ops:
                if not op.future.done():
                    op.future.set_result(value)

        return resolve

    return run


def _fail_ops(ops, exc) -> None:
    for op in ops:
        if not op.future.done():
            op.future.set_exception(exc)


def complete_changed_rows(completer: "Completer", ops: List[Op],
                          rows: List[int], parts) -> None:
    """Complete a coalesced insert run with PER-TARGET PFADD semantics: the
    kernels return changed-rows vectors; each op's bool is its own target's
    lane (one tiny D2H per run resolved on the completer — never a run-wide
    flag leaking across sketches, never a dispatcher-side device wait).
    Shared by the single-chip and pod backends."""
    flag = None
    if parts:
        flag = _start_d2h(functools.reduce(jnp.logical_or, parts))

    def run():
        try:
            fault_inject.fire("d2h_complete",
                              kind=ops[0].kind if ops else "",
                              target=ops[0].target if ops else "")
            host = None if flag is None else np.asarray(flag)
        except Exception as exc:  # noqa: BLE001
            _fail_ops(ops, classify(exc, seam="d2h_complete"))
            return None

        def resolve():
            for op, r in zip(ops, rows):
                if not op.future.done():
                    op.future.set_result(
                        False if host is None else bool(host[r]))

        return resolve

    completer.submit(run)


def backend_names(store: SketchStore, extra_names, pattern: str = "*"):
    """Store keys plus backend-held names (bank HLLs) matching `pattern` —
    the RKeys listing for backends whose objects span both registries."""
    import fnmatch

    out = dict.fromkeys(store.keys(pattern))
    for n in extra_names:
        if pattern in (None, "*") or fnmatch.fnmatchcase(n, pattern):
            out[n] = None
    return list(out)


class EpochReadCache:
    """Epoch-stamped memo for device-read results — the analogue of the
    reference's client-side caching (RLocalCachedMap invalidation topic):
    every target carries a monotonically increasing write epoch, and a read
    result (`hll_count`, BITCOUNT, bloom contains/count) is valid exactly
    while its target's epoch is unchanged. Repeated reads between writes
    skip the device entirely; any write path bumps the epoch, which is the
    whole invalidation protocol — no topic, no TTL.

    Thread contract: lookups happen on the dispatcher thread; `put` happens
    on the completer thread when the miss's materialization lands (stamped
    with the epoch captured at dispatch, so a racing write can never make a
    stale value servable). A small lock covers both.
    """

    _MISS = object()

    def __init__(self, max_entries: int = 1024):
        self.max_entries = max(0, int(max_entries))
        self._lock = threading.Lock()
        self._data: "OrderedDict" = OrderedDict()  # (target, kind, extra) -> (epoch, value)
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def get(self, target: str, kind: str, epoch: int, extra=None):
        """Cached value for (target, kind, extra) at `epoch`, else _MISS
        (use `is_hit`). Counts hit/miss stats."""
        if self.max_entries == 0:
            return self._MISS
        key = (target, kind, extra)
        with self._lock:
            ent = self._data.get(key)
            if ent is not None and ent[0] == epoch:
                self._data.move_to_end(key)
                self.hits += 1
                return ent[1]
            self.misses += 1
            return self._MISS

    def is_hit(self, value) -> bool:
        return value is not self._MISS

    def put(self, target: str, kind: str, epoch: int, value, extra=None) -> None:
        if self.max_entries == 0:
            return
        key = (target, kind, extra)
        with self._lock:
            ent = self._data.get(key)
            if ent is not None and ent[0] > epoch:
                return  # a fresher write already stamped this slot
            self._data[key] = (epoch, value)
            self._data.move_to_end(key)
            while len(self._data) > self.max_entries:
                self._data.popitem(last=False)

    def invalidate(self, target: str) -> None:
        """Drop every entry for a target (delete/rename — the epoch alone
        would keep them correct, this just frees the slots)."""
        with self._lock:
            stale = [k for k in self._data if k[0] == target]
            for k in stale:
                del self._data[k]

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def content_bytes(self) -> int:
        """Approximate bytes held by cached values (memstat 'cache'
        meter): array results report nbytes, scalars their host size."""
        import sys

        with self._lock:
            vals = [ent[1] for ent in self._data.values()]
        total = 0
        for v in vals:
            nb = getattr(v, "nbytes", None)
            total += int(nb) if nb is not None else sys.getsizeof(v)
        return total

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "hit_ratio": (self.hits / total) if total else 0.0,
                "entries": len(self._data),
                "max_entries": self.max_entries,
            }


class RowAllocator:
    """name -> bank-row bookkeeping shared by the single-chip and pod
    backends: free-list reuse, elastic grow-on-full, per-name mutation
    counters (durability/checkpoint dirty tracking). `grow` is the
    backend's capacity hook: called with the requested new capacity, it
    reallocates the bank and returns the (possibly rounded-up) actual
    capacity. The free list is kept sorted, so a claim inside a row range
    is one bisect, not a scan (the mesh plane claims every row so)."""

    __slots__ = ("rows", "free", "next", "versions", "capacity", "_grow")

    def __init__(self, capacity: int, grow: Callable[[int], int]):
        self.rows: dict = {}
        self.free: list = []
        self.next = 0
        self.versions: dict = {}
        self.capacity = capacity
        self._grow = grow

    def row_of(self, name: str, prefer=None) -> int:
        row = self.rows.get(name)
        if row is not None:
            return row
        if prefer is not None:
            row = self.claim_range(*prefer)
            if row is not None:
                self.rows[name] = row
                return row
        if self.free:
            row = self.free.pop()
        else:
            if self.next >= self.capacity:
                self.capacity = self._grow(self.capacity * 2)
            row = self.next
            self.next += 1
        self.rows[name] = row
        return row

    def claim_range(self, lo: int, hi: int) -> Optional[int]:
        """Allocate a free row inside [lo, hi), or None when the range is
        full (mesh plane row placement: a shard's preferred device-local
        block; callers fall back to anywhere-allocation on None)."""
        i = bisect.bisect_left(self.free, lo)
        if i < len(self.free) and self.free[i] < hi:
            return self.free.pop(i)
        if lo <= self.next < hi:
            row = self.next
            self.next += 1
            return row
        if self.next < lo <= hi - 1 and hi <= self.capacity:
            # Skip the watermark forward into the block; the skipped rows
            # stay allocatable through the free list (every free row lies
            # below the watermark, so the list stays sorted).
            self.free.extend(range(self.next, lo))
            self.next = lo + 1
            return lo
        return None

    def release(self, name: str) -> Optional[int]:
        """Free the name's row for reuse; returns it (None if absent)."""
        row = self.rows.pop(name, None)
        if row is not None:
            self.free_row(row)
            self.versions.pop(name, None)
        return row

    def free_row(self, row: int) -> None:
        bisect.insort(self.free, row)

    def bump(self, name: str) -> None:
        self.versions[name] = self.versions.get(name, 0) + 1

    def clear(self) -> None:
        self.rows.clear()
        self.free.clear()
        self.versions.clear()
        self.next = 0


class LinkProfile:
    """One-time measurement of the host->device link and the native fold.

    When device_put streams fast, raw keys (8 B/key) belong on the device
    where hashing runs at HBM speed; when the link is slow, the native
    fold plus a 16 KB sketch transfer wins. This probe decides which,
    once per device.
    """

    def __init__(self, device):
        import time

        import jax
        import jax.numpy as jnp

        from redisson_tpu import native as native_mod

        # Two rules keep this probe honest:
        #   * incompressible payload — a zeros buffer may measure a
        #     compressor, not the link;
        #   * force a full round trip (upload -> device reduce -> scalar
        #     sync) — block_until_ready on a bare device_put returns before
        #     the bytes actually move there, reporting fictional bandwidth
        #     that made the auto policy flip per process.
        # The fixed sync round trip cancels in the big-minus-small
        # difference; the big buffer must dwarf it or the difference
        # drowns.
        rng = np.random.default_rng(0)
        small = rng.integers(0, 256, 1 << 12, np.uint8)  # 4 KB
        big = rng.integers(0, 256, 1 << 23, np.uint8)  # 8 MB

        def roundtrip(buf):
            t0 = time.perf_counter()
            # graftlint: allow-sync(link probe times the blocking roundtrip on purpose) allow-int-reduce(probe buffer is 8 MB of uint8 so the sum is far below 2^31)
            float(jnp.sum(jax.device_put(buf, device).astype(jnp.int32)))
            return time.perf_counter() - t0

        roundtrip(small), roundtrip(big)  # warm path/alloc/compile
        t_small = min(roundtrip(small) for _ in range(2))
        t_big = min(roundtrip(big) for _ in range(2))
        self.transfer_ns_per_byte = max(
            (t_big - t_small) * 1e9 / (big.nbytes - small.nbytes), 0.001)

        self.fold_ns_per_key = float("inf")
        if native_mod.available():
            keys = np.arange(1 << 19, dtype=np.uint64)
            regs = np.zeros(16384, np.uint8)
            native_mod.hll_fold_u64(keys, regs, 0)  # warm (first-call jitter)
            t0 = time.perf_counter()
            native_mod.hll_fold_u64(keys, regs, 0)
            self.fold_ns_per_key = (time.perf_counter() - t0) * 1e9 / keys.shape[0]

    @property
    def prefer_hostfold(self) -> bool:
        return self.fold_ns_per_key < self.transfer_ns_per_byte * 8


_LINK_PROFILES: dict = {}
_LINK_LOCK = threading.Lock()


def link_profile(device) -> LinkProfile:
    with _LINK_LOCK:
        prof = _LINK_PROFILES.get(device)
        if prof is None:
            prof = _LINK_PROFILES[device] = LinkProfile(device)
        return prof


# Below this, per-run fixed costs (kernel dispatch, 16 KB sketch transfer)
# dominate either way and the raw-key path keeps read-side semantics simple.
HOSTFOLD_MIN_KEYS = 1 << 16


def ingest_rule(ingest: str, nkeys: int, device, *, bits: bool = False,
                group: bool = False, family: str = "m3",
                hll_impl: str = "scatter", plane_bytes: int = 0,
                raw_per_key: int = 8) -> str:
    """THE ingest decision, shared by the backend and any reporter (bench):
    duplicating these gates drifts.

    A forced `ingest` value picks its path. Under "auto" a batch of
    `nkeys` folds on the host only when the native library is loaded,
    the batch holds at least HOSTFOLD_MIN_KEYS keys and the measured link
    makes shipping a folded plane cheaper than shipping the raw keys:
    - an HLL batch (murmur3 `family` only) folds when the native fold
      beats 8 B/key on the link: 'delta' for a per-target `group`
      (retired through the fused window merge), 'hostfold' for a classic
      run; otherwise the device insert `hll_impl`;
    - a bloom/bitset `group` (`bits`) folds into a 'delta' plane when the
      fold plus its upload (`plane_bytes`, or its sparse encoding if
      smaller) beats `raw_per_key` bytes a key; otherwise 'scatter'."""
    if bits:
        if ingest == "segment":
            return "segment"
        if ingest == "delta":
            return "delta" if group else "scatter"
        if ingest != "auto":
            return "scatter"
        fallback = "scatter"
    elif ingest == "auto":
        fallback = hll_impl
    elif ingest == "delta":
        return "delta" if group else hll_impl
    elif ingest == "device":
        return hll_impl
    else:  # hostfold, or a forced device insert kernel
        return ingest
    from redisson_tpu import native as native_mod

    if (nkeys < HOSTFOLD_MIN_KEYS or family == "redis"
            or not native_mod.available()):
        return fallback
    prof = link_profile(device)
    if bits:
        if not (group and plane_bytes):
            return "scatter"
        ship = min(plane_bytes, nkeys * delta_mod.SPARSE_ENTRY_BYTES)
        fold_ns = prof.fold_ns_per_key + prof.transfer_ns_per_byte * ship / nkeys
        return ("delta" if fold_ns < prof.transfer_ns_per_byte * raw_per_key
                else "scatter")
    if not prof.prefer_hostfold:
        return hll_impl
    return "delta" if group else "hostfold"


class RuleInputs:
    """The measured inputs of `ingest_rule` for one device, for reports:
    {} until something probed the link (this never probes it)."""

    def __init__(self, device):
        self.device = device

    def table(self) -> dict:
        with _LINK_LOCK:
            prof = _LINK_PROFILES.get(self.device)
        if prof is None:
            return {}
        fold = prof.fold_ns_per_key
        return {"transfer_ns_per_byte": prof.transfer_ns_per_byte,
                "fold_ns_per_key": fold if fold != float("inf") else None,
                "fold_min_keys": HOSTFOLD_MIN_KEYS}


class TpuBackend:
    """Op interpreter over a SketchStore (bitset/bloom state) plus a shared
    HLL bank: every named HLL is a row of ONE [S, m] device array
    (engine.hll_bank_*), so countWith/mergeWith over hundreds of sketches is
    a single gather+row-max kernel — the reference treats mergeWith/countWith
    as first-class API (`RedissonHyperLogLog.java:40-97`), so the <50 ms
    merge target must hold through this path, not just at kernel level
    (VERDICT r3 weak #1). hll_add coalesces across targets (GLOBAL_COALESCE:
    one device call carries keys for many sketches via a per-key row
    vector, like the pod tier's bank_insert)."""

    GLOBAL_COALESCE = frozenset({"hll_add", "bloom_add", "bitset_set",
                                 "geo_merge"})

    #: Cross-target steal aliasing for the executor: all three delta kinds
    #: share one gate group, so one pipeline window may stack hll_add,
    #: bloom_add and bitset_set runs for many targets into a SINGLE fused
    #: delta-merge launch (ingest/delta.py + engine.delta_merge_stack).
    #: geo_merge (pre-folded remote site planes, geo/) shares the group:
    #: remote convergence rides the same fused launch as local writes —
    #: one batched semilattice max per window regardless of remote op count.
    COALESCE_GROUPS = {"hll_add": "delta", "bloom_add": "delta",
                       "bitset_set": "delta", "geo_merge": "delta"}

    #: run() commits all observable state (store swaps, bank mutation, row
    #: versions) on the dispatcher thread before returning — only result
    #: materialization trails on the completer. The executor's pipeline may
    #: therefore release per-target gates at stage time and keep multiple
    #: runs in flight without breaking read-your-writes.
    DISPATCH_TIME_STATE = True

    #: device index math (ops/bloom._mod_u64) is only exact for m <= 2^31 or
    #: power-of-two m — models fail bloom sizing fast when this tier backs them
    BLOOM_STRICT_MOD = True

    #: accepted `ingest` config values — "auto" follows `ingest_rule`;
    #: "device" forces the device path with the configured hll_impl; the
    #: kernel names force that device insert; "hostfold" forces the native
    #: fold; "delta" forces the host-folded delta-plane path for the three
    #: foldable write kinds (hll_add/bloom_add/bitset_set).
    INGEST_CHOICES = ("auto", "device", "hostfold", "delta", "scatter",
                      "sort", "segment")

    def __init__(
        self,
        store: SketchStore,
        hll_impl: str = "scatter",
        seed: int = 0,
        ingest: str = "auto",
        bank_capacity: int = 256,
        hll_hash: str = "murmur3",
        read_cache_entries: int = 1024,
    ):
        if ingest not in self.INGEST_CHOICES:
            raise ValueError(f"unknown ingest policy: {ingest!r}")
        if hll_hash not in ("murmur3", "redis"):
            raise ValueError(f"unknown hll_hash family: {hll_hash!r}")
        # Kernel-side family token: 'm3' (framework-native murmur3 x64 128)
        # or 'redis' (MurmurHash64A 0xadc83b19 — registers a real server can
        # keep PFADDing into; VERDICT r4 missing #3).
        self.family = "m3" if hll_hash == "murmur3" else "redis"
        if self.family == "redis" and ingest in ("hostfold", "delta"):
            raise ValueError(
                f"hll_hash='redis' is incompatible with ingest={ingest!r} "
                "(the native fold kernel implements the murmur3 family); "
                "use ingest='device' or 'auto'")
        if ingest in ("hostfold", "delta"):
            from redisson_tpu import native as native_mod

            if not native_mod.available():
                # Fail loudly: silently shipping 8 B/key over the link the
                # operator explicitly routed around would be a large,
                # invisible regression (invalid strings raise, so must an
                # unsatisfiable valid one).
                raise RuntimeError(
                    f"ingest={ingest!r} requires the native library "
                    "(native/librtpu.so failed to build/load); use "
                    "ingest='auto' to fall back automatically"
                )
        self.store = store
        self.hll_impl = hll_impl
        self.seed = seed
        self.ingest = ingest
        # the benchmark logs this table as its `# planner:` line
        self.planner = RuleInputs(store.device)
        # Host staging (pad + device_put) of chunk N+1 overlaps device
        # dispatch of chunk N for multi-chunk runs (ingest/pipeline).
        self._pipeline = StagingPipeline(depth=2)
        # HLL bank: lazy [S, m] int32 device array + shared row bookkeeping.
        self.bank = None
        self._alloc = RowAllocator(max(1, bank_capacity), self._grow_bank)
        # name -> packed host replica of a bloom filter (see the Bloom host
        # mirror section).
        self._bloom_mirrors: dict = {}
        # Epoch-stamped read memoization (client-side-caching analogue).
        # Epochs live here, not on store objects: they must also cover bank
        # rows (no store object) and host-mirror writes (store version
        # unchanged), so one counter per name is the single truth.
        self._epochs: dict = {}
        self.read_cache = EpochReadCache(read_cache_entries)
        # Delta-ingest counters (cumulative; backend.* gauges + bench read
        # these through ingest_stats()).
        self.counters = {
            "link_bytes": 0,      # delta bytes actually shipped H2D
            "raw_bytes": 0,       # bytes the raw-key path would have shipped
            "delta_fold_s": 0.0,  # host fold wall time (dispatcher side)
            "merge_launches": 0,  # fused delta_merge_stack launches
            "delta_runs": 0,      # executor runs retired via the delta path
            "delta_keys": 0,      # keys folded into delta planes
            "delta_scratch_bytes": 0,  # in-flight delta plane bytes (meter)
            "window_launches": 0,  # device dispatches issued retiring those
            "launch_us": 0.0,     # host wall time spent issuing them
            "geo_planes": 0,      # remote site planes through the fused path
            "geo_classic": 0,     # remote planes absorbed via the fallback
            "collective_merges": 0,    # PFMERGE/count runs via mesh collectives
            "bank_add_launches": 0,    # multi-target HLL bank inserts
            "bank_add_rows": 0,        # distinct bank rows those touched
            "mesh_add_launches": 0,    # HLL inserts into the mesh bank
            "mesh_add_rows": 0,        # distinct rows those touched
            "mesh_add_block_rows": 0,  # ... padded to their block size
        }
        # adds d2h_wait_ns / resolve_ns / runs_materialized to the counters
        self.completer = Completer(counters=self.counters)
        self._scratch_lock = threading.Lock()
        # memstat ledger (MemLedger-shaped); bank lifecycle hooks feed it.
        self.accounting = None
        # Mesh data plane (cluster data_plane="mesh"): attach_mesh installs
        # the ShardedBank geometry BEFORE the lazy bank exists; None in
        # every single-engine mode and the stacks plane.
        self.mesh = None
        self._sharded_bank = None
        self._shard_of = None

    # row-map views (tests and the durability duck type read these)
    @property
    def _rows(self) -> dict:
        return self._alloc.rows

    @property
    def _row_versions(self) -> dict:
        return self._alloc.versions

    @property
    def bank_capacity(self) -> int:
        return self._alloc.capacity

    @bank_capacity.setter
    def bank_capacity(self, v: int) -> None:
        self._alloc.capacity = v

    def attach_mesh(self, mesh, num_shards: int, shard_of=None) -> None:
        """Switch the (still-lazy) HLL bank onto a device mesh: rows
        sharded across `mesh` via NamedSharding(mesh, P("slots")), with
        per-logical-shard preferred row blocks so a shard's sketches stay
        device-local. `shard_of` maps a target name to its logical shard
        (per-shard row placement and memstat attribution). Must be
        called before the first bank-touching op (the client/manager wire
        it right after construction, before the executor starts)."""
        from redisson_tpu.parallel.mesh import ShardedBank

        if self.bank is not None:
            raise RuntimeError("attach_mesh: bank already materialized")
        sb = ShardedBank(mesh, self._alloc.capacity, num_shards)
        self.mesh = mesh
        self._sharded_bank = sb
        self._shard_of = shard_of
        self._alloc.capacity = sb.capacity

    def _put(self, arr):
        """Commit a bank-kernel operand: replicated across the mesh in
        mesh mode (a jit may not mix mesh-sharded and single-device
        committed inputs), on the store device otherwise."""
        import jax

        if self._sharded_bank is not None:
            return self._sharded_bank.replicate(arr)
        return jax.device_put(arr, self.store.device)

    def mesh_relocate(self, names, target_shard: int) -> int:
        """Device-side bank-row relocation for mesh-mode slot migration:
        move each name's registers into the adopting shard's preferred
        row block (copy row -> zero old -> remap allocator). MUST run on
        the dispatcher thread (executor.execute_barrier) — the caller's
        journaled flip fence orders it against in-flight windows exactly
        like the stacks plane's migration. A full target block leaves
        rows in place (placement is a perf hint; collectives mask by row
        index, so results are unchanged). Returns rows moved."""
        sb = self._sharded_bank
        if sb is None or self.bank is None:
            return 0
        lo, hi = sb.block(int(target_shard), self._alloc.capacity)
        moved = 0
        for name in names:
            row = self._alloc.rows.get(name)
            if row is None or lo <= row < hi:
                continue
            new = self._alloc.claim_range(lo, hi)
            if new is None:
                break
            regs = engine.hll_bank_row(self.bank, np.int32(row))
            self.bank = engine.hll_bank_set_row(
                self.bank, regs, np.int32(new))
            self.bank = engine.hll_bank_zero_row(self.bank, np.int32(row))
            self._alloc.rows[name] = new
            self._alloc.free_row(row)
            self._bump(name)
            moved += 1
        if moved:
            self._account_bank()
        return moved

    def mesh_occupancy(self) -> int:
        """Mesh-wide non-empty bank row count via one psum collective
        (the DBSIZE analogue for the sharded bank); 0 off-mesh/empty."""
        if self.mesh is None or self.bank is None:
            return 0
        # graftlint: allow-sync(management DBSIZE-style stat; blocking read is the contract)
        return int(engine.hll_bank_occupancy_collective(
            self.bank, mesh=self.mesh))

    def _grow_bank(self, new_cap: int) -> int:
        """RowAllocator grow hook: double the device bank in place."""
        sb = self._sharded_bank
        if sb is not None:
            new_cap = sb.round_capacity(new_cap)
            bank = self._ensure_bank()
            sb.capacity = new_cap
            self.bank = engine.hll_bank_grow_mesh(bank, new_cap, mesh=sb.mesh)
        else:
            self.bank = engine.hll_bank_grow(self._ensure_bank(), new_cap)
        self._account_bank()
        return new_cap

    def _account_bank(self) -> None:
        """Report the shared HLL bank's device bytes to the memstat
        ledger (create/grow/flushall are the only size changes). In mesh
        mode the bank is reported as per-(shard, kind) entries — each
        allocated row's bytes attribute to the logical shard owning its
        target name — so memory_stats() rollups stay exact per shard."""
        acct = self.accounting
        if acct is None:
            return
        nbytes = int(self.bank.nbytes) if self.bank is not None else 0
        sb = self._sharded_bank
        if sb is None:
            acct.set_bank_bytes(nbytes)
            return
        set_shard = getattr(acct, "set_bank_shard_bytes", None)
        if set_shard is None:  # ledger predating mesh accounting
            acct.set_bank_bytes(nbytes)
            return
        cap = max(self._alloc.capacity, 1)
        row_bytes = nbytes // cap if nbytes else 0
        shard_of = self._shard_of
        by_shard: dict = {}
        assigned = 0
        for name, _row in self._alloc.rows.items():
            shard = int(shard_of(name)) if shard_of is not None else 0
            by_shard[shard] = by_shard.get(shard, 0) + row_bytes
            assigned += row_bytes
        set_shard(by_shard, unassigned=nbytes - assigned)

    def _plan_ingest(self, nkeys: int, allow_delta: bool = False) -> str:
        """One run's HLL insert path: 'delta', 'hostfold' or a device
        insert impl ('scatter' | 'sort' | 'segment'), by `ingest_rule`.
        `allow_delta` marks calls from the delta dispatch (ops already
        proven host-foldable), which retire a fold through the fused
        multi-target merge; classic callers keep the per-target
        'hostfold' absorb."""
        return ingest_rule(self.ingest, nkeys, self.store.device,
                           group=allow_delta, family=self.family,
                           hll_impl=self.hll_impl)

    def _plan_bits(self, nkeys: int, plane_bytes: int = 0,
                   raw_per_key: int = 8, allow_delta: bool = False) -> str:
        """Set-bits strategy for bloom/bitset device inserts ('scatter' |
        'segment' | 'delta'), by `ingest_rule`; hostfold for blooms is
        decided separately by the host-mirror policy."""
        return ingest_rule(self.ingest, nkeys, self.store.device, bits=True,
                           group=allow_delta, plane_bytes=plane_bytes,
                           raw_per_key=raw_per_key)

    # -- dispatch -----------------------------------------------------------

    def run(self, kind: str, target: str, ops: List[Op]) -> None:
        if kind in self.COALESCE_GROUPS:
            # Group-coalesced runs may span kinds AND targets (the executor
            # steals same-group queue heads); the delta dispatch splits the
            # run into host-foldable planes vs classic per-(kind, target)
            # fallbacks.
            self._delta_dispatch(target, ops)
            return
        handler = getattr(self, "_op_" + kind, None)
        if handler is None:
            raise ValueError(f"unknown op kind: {kind}")
        handler(target, ops)

    # -- helpers ------------------------------------------------------------

    def _coalesce_bytes(self, ops: List[Op]):
        """Concatenate byte-key payloads; returns (data, lengths, spans)."""
        widths = {op.payload["data"].shape[1] for op in ops}
        w = max(widths)
        total = sum(op.payload["data"].shape[0] for op in ops)
        data = np.zeros((total, w), np.uint8)
        lengths = np.zeros((total,), np.int32)
        spans = []
        pos = 0
        for op in ops:
            d = op.payload["data"]
            n = d.shape[0]
            data[pos : pos + n, : d.shape[1]] = d
            lengths[pos : pos + n] = op.payload["lengths"]
            spans.append((pos, pos + n))
            pos += n
        return data, lengths, spans

    # -- Delta ingest (host-folded planes, fused multi-target merge) --------
    #
    # The three group-coalesced write kinds share one retire path: each
    # (target, kind) group in a pipeline window folds ON THE HOST into a
    # dense per-target delta plane (HLL: m-byte register-max image; bloom/
    # bitset: packed bit plane — ingest/delta.py), the planes ship instead
    # of the raw key batches, and every plane in the window becomes a row
    # of ONE [T, L] uint8 cell stack merged by a single fused elementwise
    # max launch (engine.delta_merge_stack). No scatter on the hot path:
    # the merge is bandwidth-bound, and with the executor's in-flight
    # pipelining window k+1 folds on the host while window k merges on
    # device.

    #: cell budget (padded T x padded L uint8 cells) for one merge launch;
    #: windows whose planes exceed this split into multiple launches.
    DELTA_STACK_CELLS = 1 << 26

    def _delta_eligible(self, op: Op) -> bool:
        if self.ingest not in ("auto", "delta"):
            return False
        if op.kind == "hll_add" and self.family == "redis":
            return False  # native fold kernels implement the murmur3 family
        return delta_mod.foldable(op.kind, op.payload)

    def _delta_planned(self, kind: str, tname: str, tops: List[Op]) -> bool:
        """Per-target delta gate: the target must be type-clean for the
        delta path (WRONGTYPE / uninitialized-filter errors surface
        through the classic handlers, which isolate them per target) and
        the ingest rule must pick 'delta' for this batch size."""
        if kind == "geo_merge":
            # Remote planes arrive pre-folded: shipping them is sunk cost,
            # so no rule consult — only the per-target type gates.
            inner = tops[0].payload["inner"]
            if inner == "hll_add":
                # a name that holds a bitset/bloom is WRONGTYPE
                return tname in self._rows or self.store.get(tname) is None
            if tname in self._rows:
                return False  # name holds an hll: WRONGTYPE
            obj = self.store.get(tname)
            if inner == "bloom_add":
                return obj is None or (obj.otype == ObjectType.BLOOM
                                       and not obj.meta.get("blocked"))
            return obj is None or obj.otype == ObjectType.BITSET
        nkeys = sum(op.nkeys or delta_mod.payload_nkeys(kind, op.payload)
                    for op in tops)
        if kind == "hll_add":
            if tname not in self._rows and self.store.get(tname) is not None:
                return False  # name holds a bitset/bloom: WRONGTYPE
            return self._plan_ingest(nkeys, allow_delta=True) == "delta"
        if tname in self._rows:
            return False  # name holds an hll: WRONGTYPE
        obj = self.store.get(tname)
        if kind == "bloom_add":
            if (obj is None or obj.otype != ObjectType.BLOOM
                    or obj.meta.get("blocked")):
                return False
            # A valid host mirror folds with ZERO link traffic — under
            # auto that dominates shipping any plane; forced delta keeps
            # the device copy current instead.
            if self.ingest != "delta" and self._bloom_use_host(
                    tname, obj, nkeys):
                return False
            m = obj.meta["size"]
            return self._plan_bits(nkeys, plane_bytes=(m + 7) // 8,
                                   raw_per_key=8, allow_delta=True) == "delta"
        # bitset_set — plane size is the post-growth allocation
        if obj is not None and obj.otype != ObjectType.BITSET:
            return False
        nbits = obj.state.shape[0] if obj is not None else 1024
        mx = self._max_index(tops)
        if mx >= nbits:
            nbits = max(1024, 1 << int(mx).bit_length())
        return self._plan_bits(nkeys, plane_bytes=(nbits + 7) // 8,
                               raw_per_key=4, allow_delta=True) == "delta"

    def _delta_dispatch(self, target: str, ops: List[Op]) -> None:
        """Split a (possibly cross-kind, cross-target) coalesced run into
        per-(target, kind) groups and route each whole group either
        through the fused delta window or the classic handlers — a
        target's ops never split across the two paths (its plane would
        interleave with a classic kernel on the same state mid-run)."""
        groups: "OrderedDict[tuple, List[Op]]" = OrderedDict()
        for op in ops:
            groups.setdefault((op.target, op.kind), []).append(op)
        delta_groups, classic = [], []
        for (tname, kind), tops in groups.items():
            if (all(self._delta_eligible(op) for op in tops)
                    and self._delta_planned(kind, tname, tops)):
                delta_groups.append((tname, kind, tops))
            else:
                classic.extend(tops)
        if delta_groups:
            self._delta_window(delta_groups)
        if classic:
            self._classic_group_run(classic)

    def _classic_group_run(self, ops: List[Op]) -> None:
        """Classic fallback for group-coalesced runs: hll_add's handler is
        already multi-target; bloom/bitset handlers are single-target, so
        those dispatch per target with per-target failure isolation (one
        bad name must not poison a stolen run)."""
        hll_ops = [op for op in ops if op.kind == "hll_add"]
        if hll_ops:
            try:
                self._op_hll_add(hll_ops[0].target, hll_ops)
            except Exception as exc:  # noqa: BLE001 — never strand futures
                exc = classify(exc, seam="kernel_launch")
                for op in hll_ops:
                    if not op.future.done():
                        op.future.set_exception(exc)
        rest: "OrderedDict[tuple, List[Op]]" = OrderedDict()
        for op in ops:
            if op.kind != "hll_add":
                rest.setdefault((op.kind, op.target), []).append(op)
        for (kind, tname), tops in rest.items():
            try:
                getattr(self, "_op_" + kind)(tname, tops)
            except Exception as exc:  # noqa: BLE001 — per-target isolation
                exc = classify(exc, seam="kernel_launch")
                for op in tops:
                    if not op.future.done():
                        op.future.set_exception(exc)

    def _delta_window(self, groups) -> None:
        """Fold every (target, kind) group into its delta plane, then
        retire the window through as few chunked merge launches as the
        stack budget allows (normally one)."""
        t0 = time.perf_counter()
        planes, specs = [], []
        for tname, kind, tops in groups:
            try:
                plane, spec = self._delta_fold_group(tname, kind, tops)
            except Exception as exc:  # noqa: BLE001 — per-target isolation
                # Host fold failure: nothing reached the device — retryable.
                exc = classify(exc, seam="stage_h2d")
                for op in tops:
                    if not op.future.done():
                        op.future.set_exception(exc)
                continue
            planes.append(plane)
            specs.append(spec)
        self.counters["delta_fold_s"] += time.perf_counter() - t0
        if not planes:
            return
        for p in planes:
            self.counters["raw_bytes"] += p.raw_bytes
            self.counters["delta_keys"] += p.nkeys
            self.counters["link_bytes"] += p.link_bytes
        self.counters["delta_runs"] += 1
        # Partition into merge chunks under the cell budget; sorting by
        # cell count packs similar-sized planes together so small planes
        # never pad to a huge neighbour's lane count.
        order = sorted(range(len(planes)), key=lambda i: planes[i].cells)
        chunks: List[List[int]] = []
        cur: List[int] = []
        for i in order:
            lmax = self._pad_cells(max(
                [planes[j].cells for j in cur] + [planes[i].cells]))
            t2 = 1 << (len(cur)).bit_length()  # pow2 ceil of len(cur) + 1
            if cur and t2 * lmax > self.DELTA_STACK_CELLS:
                chunks.append(cur)
                cur = [i]
            else:
                cur.append(i)
        if cur:
            chunks.append(cur)
        for chunk in chunks:
            try:
                self._delta_merge_chunk([planes[i] for i in chunk],
                                        [specs[i] for i in chunk])
            except Exception as exc:  # noqa: BLE001
                exc = classify(exc, seam="kernel_launch")
                for i in chunk:
                    for op in specs[i]["ops"]:
                        if not op.future.done():
                            op.future.set_exception(exc)

    @staticmethod
    def _pad_cells(cells: int) -> int:
        """Lane count a plane pads to in the merge stack (pow2, floored at
        the engine bucket so tiny bitsets share one compiled shape)."""
        return max(engine.MIN_BUCKET,
                   1 << max(0, int(cells - 1).bit_length()))

    def _delta_fold_group(self, tname: str, kind: str, tops: List[Op]):
        """Fold one (target, kind) group into its DeltaPlane + completion
        spec. Runs entirely on the host (native folds / numpy); any
        device work it queues (bitset pre-merge pack) is async."""
        from redisson_tpu import native as native_mod

        if kind == "geo_merge":
            return self._geo_fold_group(tname, tops)
        payloads = [op.payload for op in tops]
        nkeys = sum(delta_mod.payload_nkeys(kind, p) for p in payloads)
        raw = sum(delta_mod.payload_raw_bytes(kind, p) for p in payloads)
        if kind == "hll_add":
            self._hll_row(tname)  # allocate the bank row (may grow bank)
            plane = delta_mod.fold_hll(payloads, self.seed)
            dp = delta_mod.encode(kind, tname, plane, cells=delta_mod.HLL_M,
                                  packed=False, nkeys=nkeys, raw_bytes=raw)
            return dp, {"kind": kind, "ops": tops}
        if kind == "bloom_add":
            obj, m, k = self._bloom_meta(tname)
            # Bring the device current first (pending mirror bits would be
            # missing from the merged old-state row), then refresh the
            # mirror so it equals the device filter exactly; the delta
            # plane is then just "bits this batch newly sets".
            self._bloom_device_sync(tname)
            obj = self.store.get(tname, ObjectType.BLOOM)
            mir = self._bloom_mirror(tname, obj, m)
            scratch = mir["bits"].copy()
            newly = []
            for p in payloads:
                # In-order in-place folds: per-key try_add bools see keys
                # earlier in the batch, exactly like _bloom_host_add.
                if "packed" in p:
                    res = native_mod.bloom_fold_u64(
                        p["packed"], scratch, k, m, self.seed)
                else:
                    res = native_mod.bloom_fold_rows(
                        p["data"], p["lengths"], scratch, k, m, self.seed)
                newly.append(res.view(np.bool_))
            plane = scratch & ~mir["bits"]
            dp = delta_mod.encode(kind, tname, plane, cells=m, packed=True,
                                  nkeys=nkeys, raw_bytes=raw)
            return dp, {"kind": kind, "ops": tops, "newly": newly,
                        "scratch": scratch, "mirror": mir}
        # bitset_set
        obj = self._bitset(tname, nbits=1024)
        mx = self._max_index(tops)
        obj = self._grow_for(obj, mx if mx >= 0 else 0)
        if mx >= 0:
            self._extend(obj, mx)
        nbits = obj.state.shape[0]
        plane = delta_mod.fold_bitset(payloads, nbits)
        # Per-key SETBIT results are the PRE-merge bits: pack the current
        # state on device and start the D2H now; the completer slices per
        # key from the packed snapshot.
        old_packed = _start_d2h(engine.bitset_pack(obj.state))
        self.counters["window_launches"] += 1
        dp = delta_mod.encode(kind, tname, plane, cells=nbits, packed=True,
                              nkeys=nkeys, raw_bytes=raw)
        return dp, {"kind": kind, "ops": tops, "old_packed": old_packed}

    # -- Geo remote planes (cross-site convergence, geo/) -------------------
    #
    # A geo_merge payload is a delta plane another site already folded:
    # {"inner": hll_add|bloom_add|bitset_set, "cells", dense "plane" or
    # sparse "idx"/"val"/"plane_bytes", "meta", "seq"/"site" stamp}. The
    # fold below only combines same-target planes (elementwise max / OR)
    # and re-encodes them as an ordinary DeltaPlane carrying the INNER
    # kind, so _delta_merge_chunk's old-row gather and writeback logic
    # serve remote applies unchanged — one fused launch per window no
    # matter how many remote ops the planes summarize.

    @staticmethod
    def _geo_plane(payload) -> np.ndarray:
        """Materialize a geo payload's dense byte plane (the link ships
        sparse (idx, val) pairs when the touched fraction is small)."""
        if "plane" in payload:
            return np.asarray(payload["plane"], np.uint8)
        plane = np.zeros((int(payload["plane_bytes"]),), np.uint8)
        idx = np.asarray(payload["idx"], np.int64)
        if idx.size:
            np.maximum.at(plane, idx, np.asarray(payload["val"], np.uint8))
        return plane

    def _geo_bloom_ensure(self, target: str, meta: dict):
        """Create the local twin of a remote bloom filter on first sight
        (the origin's init params ship with every merge plane)."""
        self._check_not_hll(target, ObjectType.BLOOM)
        obj = self.store.get(target, ObjectType.BLOOM)
        if obj is not None:
            return obj
        m = int(meta.get("size", 0))
        if m <= 0:
            raise RuntimeError(
                f"geo bloom plane for uninitialized filter '{target}' "
                "carries no size meta")
        obj = self.store.get_or_create(
            target, ObjectType.BLOOM, lambda: bitset_ops.make(m),
            {k: v for k, v in meta.items()})
        self._touch(target)
        return obj

    def _geo_fold_group(self, tname: str, tops: List[Op]):
        """Fold one target's remote planes into a DeltaPlane + geo spec
        for the fused merge (the geo_merge half of _delta_fold_group)."""
        payloads = [op.payload for op in tops]
        inner = payloads[0]["inner"]
        nkeys = sum(int(p.get("nkeys", 0)) for p in payloads)
        raw = sum(int(p.get("raw", 0)) for p in payloads)
        self.counters["geo_planes"] += len(payloads)
        spec = {"kind": "geo_merge", "geo": True, "ops": tops}
        if inner == "hll_add":
            self._hll_row(tname)  # allocate the bank row (may grow bank)
            plane = np.zeros((delta_mod.HLL_M,), np.uint8)
            for p in payloads:
                np.maximum(plane, self._geo_plane(p), out=plane)
            dp = delta_mod.encode("hll_add", tname, plane,
                                  cells=delta_mod.HLL_M, packed=False,
                                  nkeys=nkeys, raw_bytes=raw)
            return dp, spec
        if inner == "bloom_add":
            obj = self._geo_bloom_ensure(tname, payloads[0].get("meta") or {})
            m = obj.meta["size"]
            # Pending host-mirror bits must reach the device BEFORE the
            # fused merge swaps the store state (the mirror is dropped in
            # the writeback, so bits still parked there would be lost).
            self._bloom_device_sync(tname)
            plane = np.zeros(((m + 7) >> 3,), np.uint8)
            for p in payloads:
                if int(p["cells"]) != m:
                    raise RuntimeError(
                        f"geo bloom plane for '{tname}' sized {p['cells']} "
                        f"bits vs local filter {m} — sites must init bloom "
                        "filters with identical parameters")
                np.bitwise_or(plane, self._geo_plane(p), out=plane)
            dp = delta_mod.encode("bloom_add", tname, plane, cells=m,
                                  packed=True, nkeys=nkeys, raw_bytes=raw)
            return dp, spec
        # bitset_set
        obj = self._bitset(tname, nbits=1024)
        mx = max(int(p["cells"]) for p in payloads) - 1
        obj = self._grow_for(obj, max(mx, 0))
        ext = max(int((p.get("meta") or {}).get("max_idx", -1))
                  for p in payloads)
        if ext >= 0:
            self._extend(obj, ext)
        nbits = obj.state.shape[0]
        plane = np.zeros(((nbits + 7) >> 3,), np.uint8)
        for p in payloads:
            src = self._geo_plane(p)
            np.bitwise_or(plane[:src.shape[0]], src, out=plane[:src.shape[0]])
        dp = delta_mod.encode("bitset_set", tname, plane, cells=nbits,
                              packed=True, nkeys=nkeys, raw_bytes=raw)
        return dp, spec

    def _delta_merge_chunk(self, planes, specs) -> None:
        """Retire one chunk of delta planes in a single fused merge: build
        the [T, L] old/delta uint8 stacks (HLL rows gathered from the
        bank, store objects contributing their cell arrays, sparse planes
        expanded and packed planes unpacked on device), launch
        engine.delta_merge_stack once, and write every row back.

        Each chunk is its own failure unit: the kernel_launch seam fires
        per chunk, and epoch bumps happen below only for rows THIS chunk
        actually merged — a failed chunk must neither commit state nor
        invalidate the read cache of targets in other chunks."""
        import jax

        fault_inject.fire("kernel_launch", kind="delta_merge",
                          target=planes[0].target if planes else "")
        t0 = time.perf_counter()
        launches = 0
        dev = self.store.device
        lanes = max(self._pad_cells(p.cells) for p in planes)
        t = len(planes)
        t2 = 1 << max(0, (t - 1).bit_length())

        def pad_row(row, cells):
            if cells == lanes:
                return row
            return jnp.zeros((lanes,), jnp.uint8).at[:cells].set(row)

        old_rows: List = [None] * t
        hll_ix = [i for i, p in enumerate(planes) if p.kind == "hll_add"]
        rows_pad = None
        if hll_ix:
            rows_pad = jax.device_put(engine.pad_rows_repeat(np.array(
                [self._rows[planes[i].target] for i in hll_ix], np.int32)),
                dev)
            gathered = engine.hll_bank_rows_u8(self._ensure_bank(), rows_pad)
            launches += 1
            for j, i in enumerate(hll_ix):
                old_rows[i] = pad_row(gathered[j], delta_mod.HLL_M)
        for i, p in enumerate(planes):
            if p.kind != "hll_add":
                old_rows[i] = pad_row(self.store.get(p.target).state, p.cells)
        delta_rows = []
        for p in planes:
            if p.sparse:
                byte_plane = engine.delta_scatter_bytes(
                    jax.device_put(p.idx, dev), jax.device_put(p.val, dev),
                    p.plane_bytes)
                launches += 1
            else:
                byte_plane = jax.device_put(p.dense, dev)
            if p.packed:
                byte_plane = engine.delta_unpack(byte_plane, p.cells)
                launches += 1
            delta_rows.append(pad_row(byte_plane, p.cells))
        if t2 > t:  # zero rows: max-identity, changed stays False
            zero = jnp.zeros((lanes,), jnp.uint8)
            old_rows.extend([zero] * (t2 - t))
            delta_rows.extend([zero] * (t2 - t))
        merged, changed = engine.delta_merge_stack(
            jnp.stack(old_rows), jnp.stack(delta_rows))
        self.counters["merge_launches"] += 1
        launches += 1
        # Writeback. HLL rows go back to the bank in one set-scatter (the
        # row vector is the SAME padded one used for the gather, so the
        # repeated pad lanes rewrite row 0 with identical merged values).
        if hll_ix:
            regs = [merged[i, :delta_mod.HLL_M] for i in hll_ix]
            regs.extend([regs[0]] * (rows_pad.shape[0] - len(regs)))
            self.bank = engine.hll_bank_set_rows(
                self.bank, jnp.stack(regs), rows_pad)
            launches += 1
            for i in hll_ix:
                self._bump(planes[i].target)
        for i, p in enumerate(planes):
            if p.kind == "hll_add":
                continue
            self.store.swap(p.target, merged[i, :p.cells])
            self._touch(p.target)
            if p.kind == "bloom_add":
                if specs[i].get("geo"):
                    # Remote bits merged device-side only: drop the host
                    # mirror (rebuilt from the device on next use) rather
                    # than guess at its post-merge contents.
                    self._bloom_mirrors.pop(p.target, None)
                else:
                    # device == mirror + this batch == scratch, by
                    # construction
                    mir = specs[i]["mirror"]
                    mir["bits"] = specs[i]["scratch"]
                    mir["synced_dev"] = self.store.get(p.target).version
        # Observed dispatch cost (bench's launches_per_window /
        # launch_us_per_window): named kernel entry points issued above +
        # the host wall time spent issuing them (non-blocking — this is
        # the scatter-ISSUE cost, not device service time).
        self.counters["window_launches"] += launches
        self.counters["launch_us"] += (time.perf_counter() - t0) * 1e6
        flag = _start_d2h(changed)
        chunk_specs = list(zip(range(t), planes, specs))

        def run():
            try:
                fault_inject.fire("d2h_complete", kind="delta",
                                  target=planes[0].target if planes else "")
                host_changed = np.asarray(flag)
                host_old = {i: np.asarray(spec["old_packed"])
                            for i, p, spec in chunk_specs
                            if p.kind == "bitset_set"
                            and spec.get("old_packed") is not None}
            except Exception as exc:  # noqa: BLE001
                exc = classify(exc, seam="d2h_complete")
                for _i, _p, spec in chunk_specs:
                    _fail_ops(spec["ops"], exc)
                return None
            return functools.partial(resolve, host_changed, host_old)

        def resolve(host_changed, host_old):
            for i, p, spec in chunk_specs:
                if spec.get("geo"):
                    # Remote planes carry no per-key result contract: the
                    # applier only needs the apply acknowledged.
                    for op in spec["ops"]:
                        if not op.future.done():
                            op.future.set_result(True)
                elif p.kind == "hll_add":
                    # Per-target PFADD bool: did ANY register of this row
                    # rise this window (hostfold precedent).
                    v = bool(host_changed[i])
                    for op in spec["ops"]:
                        if not op.future.done():
                            op.future.set_result(v)
                elif p.kind == "bloom_add":
                    for op, newly in zip(spec["ops"], spec["newly"]):
                        if not op.future.done():
                            op.future.set_result(newly)
                else:
                    old = host_old[i]
                    for op in spec["ops"]:
                        idx = np.asarray(op.payload["idx"], np.int64)
                        bits = ((old[idx >> 3] >> (7 - (idx & 7))) & 1
                                ).astype(bool)
                        if not op.future.done():
                            op.future.set_result(bits)

        # In-flight delta plane bytes (memstat scratch meter): charged for
        # the window between launch and completion, released even when the
        # completer path fails an op.
        scratch_inflight = sum(int(p.plane_bytes) for p in planes)
        with self._scratch_lock:
            self.counters["delta_scratch_bytes"] += scratch_inflight

        def run_and_release():
            try:
                return run()
            finally:
                with self._scratch_lock:
                    self.counters["delta_scratch_bytes"] -= scratch_inflight

        self.completer.submit(run_and_release)

    def ingest_stats(self) -> dict:
        """Cumulative delta-ingest counters + the derived per-key link
        cost (bench's `delta_bytes_per_key` and the backend.* gauges read
        this) and the observed per-window dispatch cost
        (`launches_per_window` / `launch_us_per_window`)."""
        out = dict(self.counters)
        out["delta_bytes_per_key"] = (
            self.counters["link_bytes"]
            / max(self.counters["delta_keys"], 1))
        windows = self.counters["delta_runs"]
        out["launches_per_window"] = (
            self.counters["window_launches"] / max(windows, 1))
        out["launch_us_per_window"] = (
            self.counters["launch_us"] / max(windows, 1))
        return out

    def scratch_bytes(self) -> dict:
        """Host-side scratch byte meters (memstat 'scratch' category):
        bloom mirror replicas + delta planes currently in flight."""
        mirrors = 0
        for m in list(self._bloom_mirrors.values()):
            bits = m.get("bits") if isinstance(m, dict) else None
            mirrors += int(getattr(bits, "nbytes", 0) or 0)
        with self._scratch_lock:
            delta = self.counters.get("delta_scratch_bytes", 0)
        return {"bloom_mirrors": mirrors, "delta_scratch": delta}

    # -- HLL (bank-backed) --------------------------------------------------

    def _ensure_bank(self):
        if self.bank is None:
            import jax

            sb = self._sharded_bank
            if sb is not None:
                self.bank = sb.make(hll_ops.M)
            else:
                self.bank = jax.device_put(
                    engine.hll_bank_make(self.bank_capacity),
                    self.store.device)
            self._account_bank()
        return self.bank

    def _hll_row(self, name: str, create: bool = True):
        """name -> bank row (WRONGTYPE if the store holds the name as a
        bitset/bloom — the bank is the HLL half of the keyspace)."""
        row = self._alloc.rows.get(name)
        if row is not None:
            return row
        other = self.store.get(name)
        if other is not None:
            raise WrongTypeError(
                f"key '{name}' holds {other.otype}, operation needs hll"
            )
        if not create:
            return None
        self._ensure_bank()
        prefer = None
        sb = self._sharded_bank
        if sb is not None and self._shard_of is not None:
            # Mesh plane: try the owning shard's preferred row block first
            # so the row lands on that shard's mesh device (a full block
            # spills anywhere — placement is a hint, not a domain).
            prefer = sb.block(int(self._shard_of(name)),
                              self._alloc.capacity)
        return self._alloc.row_of(name, prefer=prefer)

    def _check_not_hll(self, name: str, otype: str) -> None:
        if name in self._rows:
            raise WrongTypeError(
                f"key '{name}' holds hll, operation needs {otype}"
            )

    def _bump(self, name: str) -> None:
        self._alloc.bump(name)
        self._touch(name)

    # -- read-cache epochs ---------------------------------------------------

    def _epoch(self, name: str) -> int:
        return self._epochs.get(name, 0)

    def _touch(self, name: str) -> None:
        """A write made `name`'s device/mirror state diverge from any cached
        read: bump its epoch. Every mutation path funnels through here (HLL
        via _bump; store swaps, mirror writes, import/restore and delete
        call it directly)."""
        self._epochs[name] = self._epochs.get(name, 0) + 1

    def notify_restored(self, name: str) -> None:
        """Checkpoint/snapshot restore swapped `name`'s device state in
        UNDER the op path (store.swap, not an _op_ handler): bump the
        epoch so epoch-stamped cached reads go stale, drop the entry's
        cached reads outright, and discard any host bloom mirror built
        against the pre-restore filter (it would silently serve wrong
        membership bits). Replayed journal ops need none of this — they
        re-enter through run() and touch epochs like live traffic."""
        self._bloom_mirrors.pop(name, None)
        self._touch(name)
        self.read_cache.invalidate(name)

    # durability/checkpoint surface (same duck type as PodBackend — the
    # client's _pod_backend() probe picks this up, so bank rows flush and
    # checkpoint through dispatcher-serialized hll_export/hll_import).
    def bank_names(self) -> List[str]:
        return list(self._rows)

    def row_version(self, name: str) -> int:
        return self._alloc.versions.get(name, 0)

    def names(self, pattern: str = "*") -> List[str]:
        return backend_names(self.store, self._rows, pattern)

    def _op_hll_add(self, target: str, ops: List[Op]) -> None:
        # A coalesced run may span formats AND targets (GLOBAL_COALESCE);
        # group by format — PFADD is a commutative max-fold, so regrouping
        # is safe; per-key slot vectors carry the target routing.
        #
        # Targets are validated (and rows allocated, growing the bank) up
        # front: a WRONGTYPE name fails ONLY its own ops, never poisons the
        # rest of the coalesced run, and no kernel has been dispatched for
        # an op that later turns out invalid. Fixed row set also means the
        # bank shape is stable for the whole run's kernels.
        valid = []
        for op in ops:
            try:
                self._hll_row(op.target)
            except WrongTypeError as exc:
                op.future.set_exception(exc)
                continue
            valid.append(op)
        ops = valid
        packed_ops = [op for op in ops if "packed" in op.payload]
        int_ops = [op for op in ops if "hi" in op.payload]
        byte_ops = [op for op in ops if "data" in op.payload]
        device_ops = [op for op in ops if "device_packed" in op.payload]
        host_ops = packed_ops + int_ops + byte_ops
        if host_ops:
            path = "device" if self.mesh is not None else self._plan_ingest(
                sum(op.nkeys or self._payload_nkeys(op) for op in host_ops))
            if path == "hostfold":
                self._hll_add_hostfold(host_ops)
            else:
                for group in (packed_ops, int_ops, byte_ops):
                    if group:
                        self._hll_add_group(group, path)
        if device_ops:
            self._hll_add_device(device_ops)
        leftover = [
            op for op in ops
            if not ({"packed", "hi", "data", "device_packed"}
                    & op.payload.keys())
        ]
        for op in leftover:  # fail loudly, never strand a future
            op.future.set_exception(
                ValueError(f"unknown hll_add payload keys: {sorted(op.payload)}")
            )

    def _complete_changed(self, ops: List[Op], parts) -> None:
        complete_changed_rows(
            self.completer, ops, [self._rows[op.target] for op in ops], parts)

    @staticmethod
    def _payload_nkeys(op: Op) -> int:
        p = op.payload
        for key in ("packed", "hi", "data"):
            if key in p:
                return p[key].shape[0]
        return 0

    def _hll_add_hostfold(self, ops: List[Op]) -> None:
        """Transfer-adaptive ingest: fold each target's keys into 16 KB of
        host registers with the native kernel (GIL released; ~220 M
        keys/s/core), ship the folded sketches, and absorb them into their
        bank rows with ONE batched max-scatter. The host never ships
        8 B/key across a slow link, and `changed` keeps its exact semantics
        (any register raised by this run)."""
        import jax

        from redisson_tpu import native as native_mod

        folds: dict = {}  # target -> host regs
        for op in ops:
            regs = folds.get(op.target)
            if regs is None:
                regs = folds[op.target] = np.zeros(16384, np.uint8)
            p = op.payload
            if "packed" in p:
                native_mod.hll_fold_u64(p["packed"], regs, self.seed)
            elif "hi" in p:
                keys = (p["hi"].astype(np.uint64) << np.uint64(32)) | p[
                    "lo"
                ].astype(np.uint64)
                native_mod.hll_fold_u64(keys, regs, self.seed)
            else:
                native_mod.hll_fold_rows(p["data"], p["lengths"], regs, self.seed)
        names = list(folds)
        # Pad the sketch count to a power of two: absorb compiles per [R, m]
        # shape, and zero rows absorb
        # as no-ops under max — same pad-to-bucket rule as the key batches.
        rows = engine.pad_rows_repeat(
            np.array([self._rows[n] for n in names], np.int32))
        stack = np.zeros((rows.shape[0], 16384), np.uint8)
        for i, n in enumerate(names):
            stack[i] = folds[n]
        self.bank, changed = engine.hll_bank_absorb_rows(
            self.bank, self._put(stack), self._put(rows),
        )
        for n in names:
            self._bump(n)
        # Per-target PFADD bool: lane i of `changed` is source sketch i.
        lane_of = {n: i for i, n in enumerate(names)}
        lanes = [lane_of[op.target] for op in ops]
        flag = _start_d2h(changed)

        def run():
            try:
                fault_inject.fire("d2h_complete",
                                  kind=ops[0].kind if ops else "",
                                  target=ops[0].target if ops else "")
                host = np.asarray(flag)
            except Exception as exc:  # noqa: BLE001
                _fail_ops(ops, classify(exc, seam="d2h_complete"))
                return None

            def resolve():
                for op, lane in zip(ops, lanes):
                    if not op.future.done():
                        op.future.set_result(bool(host[lane]))

            return resolve

        self.completer.submit(run)

    def _add_rows(self, ops: List[Op], nkeys, kernel, operands) -> tuple:
        """A multi-target insert run (engine._bank_add): each op's slot —
        its target's index among the run's distinct bank rows — and those
        rows, padded to a power of two of at least MIN_BLOCK_ROWS and at
        most the bank's row count; then one `kernel` launch per chunk of
        keys. `nkeys` is each op's key count and operands(s, e) the
        kernel's padded key operands for keys [s, e) and their validity.
        On the mesh plane the launches update the sharded bank, at most
        MESH_CHUNK_KEYS keys each (a fill builds one program per bank
        size). Returns (changed parts, op slots)."""
        slot_of: dict = {}
        slots = np.asarray([slot_of.setdefault(self._rows[op.target],
                                               len(slot_of)) for op in ops],
                           np.int32)
        nrows = len(slot_of)
        urows = engine.pad_rows_repeat(
            np.fromiter(slot_of, np.int32, nrows),
            floor=engine.MIN_BLOCK_ROWS, cap=self._ensure_bank().shape[0])
        slotv = np.repeat(slots, nkeys)
        mesh = self.mesh
        ids = {"rows": nrows}
        chunk = None
        if mesh is not None:
            ids["chips"] = int(mesh.devices.size)
            chunk = engine.MESH_CHUNK_KEYS
        c = self.counters
        parts = []
        for s, e in engine.chunk_spans(slotv.shape[0], chunk):
            keys, valid = operands(s, e)
            pslot, _ = engine.pad_ints(slotv[s:e])
            with stage("backend.launch", keys=e - s, **ids):
                self.bank, changed = kernel(
                    self._ensure_bank(), *keys, pslot, urows, valid,
                    self.seed, self.family, mesh=mesh)
            if mesh is None:
                c["bank_add_launches"] += 1
                c["bank_add_rows"] += nrows
            else:
                c["mesh_add_launches"] += 1
                c["mesh_add_rows"] += nrows
                c["mesh_add_block_rows"] += int(urows.shape[0])
            parts.append(changed)
        return parts, slots

    def _one_row(self, ops: List[Op]):
        """np.int32 row when every op targets one sketch (the scalar-row
        kernel fast path), else None."""
        targets = {op.target for op in ops}
        if len(targets) == 1:
            return np.int32(self._rows[next(iter(targets))])
        return None

    def _hll_add_group(self, ops: List[Op], impl: str = "scatter") -> None:
        # Kernels are only *dispatched* here; the `changed` device scalars
        # resolve on the completer thread so the dispatcher is never
        # device-bound. Single-target runs use the scalar-row kernels (no
        # per-key row vector ships over the link); multi-target coalesced
        # runs carry a per-key slot vector into the run's distinct rows —
        # one call for many sketches that touches only those rows.
        # `impl` is the ingest rule's device insert choice for this run; it
        # reaches the scalar-row kernels (the multi-target kernels stay on
        # the XLA scatter, see engine._bank_add_row). The mesh plane's
        # sharded bank takes only the multi-target kernels, whose changed
        # flags come back per slot of the run's rows.
        mesh = self.mesh
        one = self._one_row(ops) if mesh is None else None
        parts = []
        slots = None
        if "packed" in ops[0].payload:
            # Concatenating copies 8 B/key on the dispatcher, so a LARGE
            # op's buffer ships to the device as-is through the scalar-row
            # kernel (zero host copies end-to-end, no 4 B/key row vector);
            # only small ops gather into shared buckets with a slot vector.
            # Large multi-chunk runs go through the staging pipeline: a
            # worker thread pads + device_puts chunk N+1 while this thread
            # dispatches chunk N (the bank carry keeps dispatch serial).
            small: List[Op] = []
            chunks = []
            for op in ops:
                arr = op.payload["packed"]
                if mesh is not None or arr.shape[0] < engine.MIN_BUCKET:
                    small.append(op)
                    continue
                row = self._rows[op.target]
                chunks.extend(
                    (row, arr[s:e])
                    for s, e in engine.chunk_spans(arr.shape[0]))
            if chunks:
                def put_chunk(item):
                    row, chunk = item
                    prows, count = engine.pad_rows(chunk)
                    return (row, self._put(prows), np.int32(count))

                def dispatch(_i, staged):
                    row, prows, count = staged
                    with stage("backend.launch", keys=int(count)):
                        self.bank, changed = engine.hll_bank_add_packed(
                            self._ensure_bank(), prows, count, np.int32(row),
                            self.seed, self.family, impl
                        )
                    return changed

                parts.extend(self._pipeline.run(chunks, put_chunk, dispatch))
            if small:
                packed = np.concatenate(
                    [op.payload["packed"] for op in small])

                def operands(s, e):
                    pk_, count = engine.pad_rows(packed[s:e])
                    return (pk_,), np.int32(count)

                got, slots = self._add_rows(
                    small, [op.payload["packed"].shape[0] for op in small],
                    engine.hll_bank_add_packed_rows, operands)
                parts.extend(got)
        elif "hi" in ops[0].payload:
            hi = np.concatenate([op.payload["hi"] for op in ops])
            lo = np.concatenate([op.payload["lo"] for op in ops])
            if one is None:
                def operands(s, e):
                    phi, valid = engine.pad_ints(hi[s:e])
                    plo, _ = engine.pad_ints(lo[s:e])
                    return (phi, plo), valid

                parts, slots = self._add_rows(
                    ops, [op.payload["hi"].shape[0] for op in ops],
                    engine.hll_bank_add_u64_rows, operands)
            else:  # scalar row: no 4 B/key row transfer
                for s, e in engine.chunk_spans(hi.shape[0]):
                    phi, valid = engine.pad_ints(hi[s:e])
                    plo, _ = engine.pad_ints(lo[s:e])
                    with stage("backend.launch", keys=e - s):
                        self.bank, changed = engine.hll_bank_add_u64(
                            self._ensure_bank(), phi, plo, valid, one,
                            self.seed, self.family, impl
                        )
                    parts.append(changed)
        else:
            data, lengths, spans = self._coalesce_bytes(ops)
            if one is None:
                def operands(s, e):
                    pdata, plengths, valid = engine.pad_bytes(data[s:e],
                                                              lengths[s:e])
                    return (pdata, plengths), valid

                parts, slots = self._add_rows(
                    ops, [e - s for s, e in spans],
                    engine.hll_bank_add_bytes_rows, operands)
            else:
                for s, e in engine.chunk_spans(data.shape[0]):
                    pdata, plengths, valid = engine.pad_bytes(data[s:e],
                                                              lengths[s:e])
                    with stage("backend.launch", keys=e - s):
                        self.bank, changed = engine.hll_bank_add_bytes(
                            self._ensure_bank(), pdata, plengths, valid, one,
                            self.seed, self.family, impl
                        )
                    parts.append(changed)
        for op in ops:
            self._bump(op.target)
        if mesh is None:
            self._complete_changed(ops, parts)
        else:
            complete_changed_rows(self.completer, ops, list(slots), parts)

    def _hll_add_device(self, ops: List[Op]) -> None:
        """Device-resident ingest: the payload array is already on the
        chip, so each op is one kernel dispatch at its own (padded) shape —
        no host copy, no transfer, no concatenation. Row is a traced
        scalar: no per-key row vector materializes on device either. On
        the mesh plane the keys are replicated and go through the
        multi-target kernel into the sharded bank."""
        if self.mesh is not None:
            arr = jnp.concatenate([op.payload["device_packed"] for op in ops])

            def operands(s, e):
                b = engine.bucket_size(e - s)
                packed = jnp.zeros((b, 2), jnp.uint32).at[:e - s].set(
                    arr[s:e])
                return (self._put(packed),), np.int32(e - s)

            parts, slots = self._add_rows(
                ops, [int(op.payload["device_packed"].shape[0])
                      for op in ops],
                engine.hll_bank_add_packed_rows, operands)
            for op in ops:
                self._bump(op.target)
            complete_changed_rows(self.completer, ops, list(slots), parts)
            return
        parts = []
        for op in ops:
            row = self._rows[op.target]
            arr = op.payload["device_packed"]
            for s, e in engine.chunk_spans(int(arr.shape[0])):
                packed = arr[s:e]
                n = e - s
                b = engine.bucket_size(n)
                if n != b:
                    packed = jnp.zeros((b, 2), jnp.uint32).at[:n].set(packed)
                self.bank, changed = engine.hll_bank_add_packed(
                    self._ensure_bank(), packed, np.int32(n), np.int32(row),
                    self.seed, self.family
                )
                parts.append(changed)
            self._bump(op.target)
        self._complete_changed(ops, parts)

    def _op_hll_count(self, target: str, ops: List[Op]) -> None:
        row = self._hll_row(target, create=False)
        if row is None:
            # Absent targets are never cached: creation does not bump the
            # epoch, so a cached 0 could outlive the first insert.
            for op in ops:
                op.future.set_result(0)
            return
        epoch = self._epoch(target)
        cached = self.read_cache.get(target, "hll_count", epoch)
        if self.read_cache.is_hit(cached):
            # No kernel, no D2H — but still resolve via the completer so
            # per-target results stay FIFO behind reads already in flight.
            _trace_cache(ops, hit=True)
            self.completer.submit(_complete_all(ops, lambda v=cached: v))
            return
        _trace_cache(ops, hit=False)
        # async dispatch; D2H starts now, sync happens off-thread
        with stage("backend.launch", ops=len(ops)):
            if self.mesh is not None:
                # a row of the sharded bank: read where it lies, no gather
                est = _start_d2h(engine.hll_bank_count_rows_collective(
                    self.bank, np.array([row], np.int32), mesh=self.mesh))
            else:
                est = _start_d2h(engine.hll_bank_count(self.bank,
                                                       np.int32(row)))

        def materialize(est=est, epoch=epoch):
            # graftlint: allow-sync(completer thread: blocking materialization is this thread's job)
            v = int(round(float(est)))
            # Stamped with the dispatch-time epoch: a write that raced in
            # since then bumped the live epoch, so this entry can't serve.
            self.read_cache.put(target, "hll_count", epoch, v)
            return v

        self.completer.submit(_complete_all(ops, materialize))

    def _op_hll_export(self, target: str, ops: List[Op]) -> None:
        """(registers uint8[m], version) on the dispatcher — serialized with
        the donating insert kernels, so the read can never hit an
        invalidated buffer (the durability/checkpoint read path). The row
        gather produces a fresh array, independent of the bank buffer a
        later insert donates away."""
        row = self._hll_row(target, create=False)
        if row is None:
            for op in ops:
                op.future.set_result(None)
            return
        snapshot = _start_d2h(engine.hll_bank_row(self.bank, np.int32(row)))
        version = self._row_versions.get(target, 0)
        self.completer.submit(
            _complete_all(
                # graftlint: allow-sync(completer thread: materializing the staged snapshot is this thread's job)
                ops, lambda: (np.asarray(snapshot).astype(np.uint8), version)
            )
        )

    def _op_hll_import(self, target: str, ops: List[Op]) -> None:
        """Overwrite (or create) an HLL bank row from host registers."""
        import jax

        for op in ops:
            regs = np.asarray(op.payload["regs"]).astype(np.int32)
            row = self._hll_row(target)
            self.bank = engine.hll_bank_set_row(
                self.bank, self._put(regs), np.int32(row)
            )
            self._bump(target)
            op.future.set_result(True)

    def _count_rows(self, target: str, extra_names) -> Optional[np.ndarray]:
        rows = []
        for n in (target, *extra_names):
            row = self._hll_row(n, create=False)
            if row is not None:
                rows.append(row)
        return np.array(rows, np.int32) if rows else None

    def _op_hll_count_with(self, target: str, ops: List[Op]) -> None:
        # Union count across sketches: one gather + row-max + estimator
        # kernel over the padded row vector — never mutates. Mesh plane:
        # the fold runs as a shard_map collective (per-device row max +
        # one pmax hop) — no register image crosses the host link even
        # when the rows span every logical shard.
        for op in ops:
            rows = self._count_rows(target, op.payload["names"])
            if rows is None:
                op.future.set_result(0)
                continue
            with stage("backend.launch", rows=len(rows)):
                if self.mesh is not None:
                    self.counters["collective_merges"] += 1
                    est = _start_d2h(engine.hll_bank_count_rows_collective(
                        self.bank, engine.pad_rows_repeat(rows),
                        mesh=self.mesh))
                else:
                    est = _start_d2h(engine.hll_bank_count_rows(
                        self.bank, engine.pad_rows_repeat(rows)))
            self.completer.submit(
                # graftlint: allow-sync(completer thread: materializing the staged estimate is this thread's job)
                _complete_all([op], lambda est=est: int(round(float(est))))
            )

    def _merge_rows(self, target: str, names) -> tuple:
        """(target_row, padded source-row vector incl. target) for the
        PFMERGE family — target participates in the max, missing sources
        are skipped, pad-with-repeats keeps shapes static per pow2 class."""
        trow = self._hll_row(target)
        rows = [trow] + [
            r for n in names
            if (r := self._hll_row(n, create=False)) is not None
        ]
        return np.int32(trow), engine.pad_rows_repeat(np.array(rows, np.int32))

    def _op_hll_merge_with(self, target: str, ops: List[Op]) -> None:
        # PFMERGE semantics: fold sources into target — one gather +
        # row-max + row-set kernel (target row is in the gathered set, so
        # existing target registers participate in the max).
        for op in ops:
            trow, rows = self._merge_rows(target, op.payload["names"])
            if self.mesh is not None:
                # Collective PFMERGE: device-side fold + pmax; the target
                # row's owner scatters the merged registers locally.
                self.counters["collective_merges"] += 1
                self.bank = engine.hll_bank_merge_rows_collective(
                    self.bank, rows, trow, mesh=self.mesh)
            else:
                self.bank = engine.hll_bank_merge_rows(self.bank, rows, trow)
            self._bump(target)
            op.future.set_result(None)

    def _op_hll_merge_count(self, target: str, ops: List[Op]) -> None:
        # Fused PFMERGE+PFCOUNT (one device program, one D2H sync) — the
        # blocking merge_with+count path costs one link RTT instead of
        # three (reference: single pipelined batch,
        # RedissonHyperLogLog.java:78-97).
        for op in ops:
            trow, rows = self._merge_rows(target, op.payload["names"])
            if self.mesh is not None:
                self.counters["collective_merges"] += 1
                self.bank, est = engine.hll_bank_merge_count_rows_collective(
                    self.bank, rows, trow, mesh=self.mesh)
            else:
                self.bank, est = engine.hll_bank_merge_count_rows(
                    self.bank, rows, trow)
            self._bump(target)
            est = _start_d2h(est)
            self.completer.submit(
                _complete_all([op], lambda est=est: int(round(float(est))))
            )

    # -- BitSet -------------------------------------------------------------

    def _bitset(self, name: str, nbits: int = None):
        self._check_not_hll(name, ObjectType.BITSET)
        obj = self.store.get(name, ObjectType.BITSET)
        if obj is None:
            if nbits is None:
                raise KeyError(f"bitset '{name}' does not exist")
            obj = self.store.get_or_create(
                name, ObjectType.BITSET, lambda: bitset_ops.make(nbits), {"nbits": nbits}
            )
        return obj

    @staticmethod
    def _extend(obj, max_index: int) -> None:
        """Track the WRITTEN extent in redis byte granularity: SETBIT
        extends the string to the byte holding the index, and size()/NOT
        operate on that extent, not the pow2 device allocation
        (conformance vs RedissonBitSetTest.java:82-104 size asserts)."""
        ext = ((int(max_index) // 8) + 1) * 8
        if ext > obj.meta.get("extent_bits", 0):
            obj.meta["extent_bits"] = ext

    def _grow_for(self, obj, max_index: int):
        """Redis SETBIT auto-grows the string; grow in power-of-two bytes."""
        nbits = obj.state.shape[0]
        if max_index < nbits:
            return obj
        new_bits = max(1024, 1 << (int(max_index).bit_length()))
        grown = jnp.zeros((new_bits,), jnp.uint8).at[:nbits].set(obj.state)
        obj.meta["nbits"] = new_bits
        self.store.swap(obj.name, grown)
        return self.store.get(obj.name)

    @staticmethod
    def _max_index(ops: List[Op]) -> int:
        """Largest bit index across the run, from the host-side `max_idx`
        the models stamp at payload-build time — the grow/extent decision
        must never reduce the index array inside dispatch (a device-resident
        payload would turn `int(idx.max())` into a blocking per-op sync).
        Falls back to a host numpy reduce for payloads without the stamp.
        Returns -1 for an all-empty run."""
        mx = -1
        for op in ops:
            m = op.payload.get("max_idx")
            if m is None:
                arr = op.payload["idx"]
                m = int(arr.max()) if arr.size else -1
            mx = max(mx, int(m))
        return mx

    def _bitset_mutate(self, target: str, ops: List[Op], kernel) -> None:
        idx = np.concatenate([op.payload["idx"] for op in ops])
        mx = self._max_index(ops)
        obj = self._bitset(target, nbits=1024)
        obj = self._grow_for(obj, mx if mx >= 0 else 0)
        if mx >= 0:
            self._extend(obj, mx)
        outs = []
        spans = []
        for s, e in engine.chunk_spans(idx.shape[0]):
            # uint32, not int32: positions past 2^31 wrap int32 negative
            pidx, valid = engine.pad_ints(idx[s:e].astype(np.uint32))
            with stage("backend.launch", keys=e - s):
                new, old = kernel(obj.state, pidx, valid)
            self.store.swap(target, new)
            outs.append(old)  # device handles; materialized off-thread
            spans.append(e - s)
        self._touch(target)
        self.completer.submit(self._slice_results(ops, outs, spans))

    @staticmethod
    def _slice_results(ops: List[Op], outs, spans, post=None,
                       on_result=None) -> callable:
        """Completion closure: materialize per-chunk device vectors, then
        slice per-op bool results in submission order. `post` (optional)
        transforms the concatenated host vector before slicing; `on_result`
        (optional) sees each (op, value) before the future resolves — the
        read-cache fill hook."""
        for o in outs:
            _start_d2h(o)

        def run():
            try:
                fault_inject.fire("d2h_complete",
                                  kind=ops[0].kind if ops else "",
                                  target=ops[0].target if ops else "")
                parts = [np.asarray(o)[:n] for o, n in zip(outs, spans)]
                flat = np.concatenate(parts) if parts else np.zeros((0,), np.uint8)
                if post is not None:
                    flat = post(flat)
            except Exception as exc:  # noqa: BLE001
                _fail_ops(ops, classify(exc, seam="d2h_complete"))
                return None
            return functools.partial(resolve, flat)

        def resolve(flat):
            pos = 0
            for op in ops:
                p = op.payload
                n = (p["idx"].shape[0] if "idx" in p
                     else p["packed"].shape[0] if "packed" in p
                     else p["data"].shape[0])
                if not op.future.done():
                    value = flat[pos : pos + n].astype(bool)
                    if on_result is not None:
                        on_result(op, value)
                    op.future.set_result(value)
                pos += n

        return run

    def _op_bitset_set(self, target: str, ops: List[Op]) -> None:
        self._bitset_mutate(target, ops, engine.bitset_set)

    def _op_bitset_clear(self, target: str, ops: List[Op]) -> None:
        self._check_not_hll(target, ObjectType.BITSET)
        if self.store.get(target, ObjectType.BITSET) is None:
            for op in ops:
                n = op.payload["idx"].shape[0]
                op.future.set_result(np.zeros((n,), bool))
            return
        self._bitset_mutate(target, ops, engine.bitset_clear)

    def _op_bitset_get(self, target: str, ops: List[Op]) -> None:
        self._check_not_hll(target, ObjectType.BITSET)
        obj = self.store.get(target, ObjectType.BITSET)
        idx = np.concatenate([op.payload["idx"] for op in ops])
        if obj is None:
            pos = 0
            for op in ops:
                n = op.payload["idx"].shape[0]
                op.future.set_result(np.zeros((n,), bool))
                pos += n
            return
        nbits = obj.state.shape[0]
        clipped = np.clip(idx, 0, nbits - 1).astype(np.uint32)
        outs, spans = [], []
        for s, e in engine.chunk_spans(clipped.shape[0]):
            pidx, valid = engine.pad_ints(clipped[s:e])
            outs.append(engine.bitset_get(obj.state, pidx, valid))
            spans.append(e - s)
        self.completer.submit(self._slice_results(
            ops, outs, spans, post=lambda flat: np.where(idx < nbits, flat, 0)
        ))

    def _op_bitset_cardinality(self, target: str, ops: List[Op]) -> None:
        self._check_not_hll(target, ObjectType.BITSET)
        obj = self.store.get(target, ObjectType.BITSET)
        if obj is None:
            for op in ops:
                op.future.set_result(0)
            return
        epoch = self._epoch(target)
        cached = self.read_cache.get(target, "bitset_cardinality", epoch)
        if self.read_cache.is_hit(cached):
            _trace_cache(ops, hit=True)
            self.completer.submit(_complete_all(ops, lambda v=cached: v))
            return
        _trace_cache(ops, hit=False)
        # Partials go D2H async; the 64-bit-exact combine happens at
        # completion (an int32 total wraps negative past 2^31 set bits).
        with stage("backend.launch", ops=len(ops)):
            v = _start_d2h(engine.bitset_cardinality_partials(obj.state))

        def materialize(v=v, epoch=epoch):
            out = bitset_ops.combine_partials(v)
            self.read_cache.put(target, "bitset_cardinality", epoch, out)
            return out

        self.completer.submit(_complete_all(ops, materialize))

    def _op_bitset_length(self, target: str, ops: List[Op]) -> None:
        self._check_not_hll(target, ObjectType.BITSET)
        obj = self.store.get(target, ObjectType.BITSET)
        if obj is None:
            for op in ops:
                op.future.set_result(0)
            return
        epoch = self._epoch(target)
        cached = self.read_cache.get(target, "bitset_length", epoch)
        if self.read_cache.is_hit(cached):
            _trace_cache(ops, hit=True)
            self.completer.submit(_complete_all(ops, lambda v=cached: v))
            return
        _trace_cache(ops, hit=False)
        # Same async shape as BITCOUNT: int32 local offsets go D2H, the
        # absolute position is assembled in 64-bit host ints at completion
        # (positions past 2^31 bits wrap an int32 device scalar).
        v = _start_d2h(engine.bitset_length_partials(obj.state))

        def materialize(v=v, epoch=epoch):
            out = bitset_ops.combine_length(v)
            self.read_cache.put(target, "bitset_length", epoch, out)
            return out

        self.completer.submit(_complete_all(ops, materialize))

    def _op_bitset_size(self, target: str, ops: List[Op]) -> None:
        """STRLEN * 8 — the WRITTEN byte extent, exactly what redis
        reports (not the pow2 device allocation; conformance vs
        RedissonBitSetTest.java:82-104)."""
        self._check_not_hll(target, ObjectType.BITSET)
        obj = self.store.get(target, ObjectType.BITSET)
        # Default 0, never the pow2 allocation: an object created by a
        # write-less path (range-clear on a fresh key) has no written
        # extent and redis would report STRLEN 0 (review r5).
        val = 0 if obj is None else obj.meta.get("extent_bits", 0)
        for op in ops:
            op.future.set_result(val)

    def _op_bitset_set_range(self, target: str, ops: List[Op]) -> None:
        for op in ops:
            start, end, value = op.payload["start"], op.payload["end"], op.payload["value"]
            obj = self._bitset(target, nbits=1024)
            if end > 0:
                obj = self._grow_for(obj, end - 1)
                if value:
                    # Range-CLEAR does not extend the written extent — the
                    # wire tier clamps range-clears to the current string
                    # (r4: no zero-padding writes), and the tiers must
                    # agree on size(). Single-bit clears extend on both
                    # tiers, mirroring SETBIT.
                    self._extend(obj, end - 1)
            new = bitset_ops.set_range(obj.state, start, end, value)
            self.store.swap(target, new)
            self._touch(target)
            op.future.set_result(None)

    def _op_bitset_op(self, target: str, ops: List[Op]) -> None:
        """BITOP AND/OR/XOR/NOT into target (reference and/or/xor/not)."""
        for op in ops:
            kind = op.payload["op"]
            sources = op.payload["names"]
            arrays = []
            src_objs = []
            for n in sources:
                # HLLs live in the bank, not the store: without this guard
                # an HLL source would read as absent and be silently
                # skipped instead of WRONGTYPE (review r4).
                self._check_not_hll(n, ObjectType.BITSET)
                o = self.store.get(n, ObjectType.BITSET)
                if o is not None:
                    arrays.append(o.state)
                    src_objs.append(o)
            if kind == "not":
                obj = self.store.get(target, ObjectType.BITSET)
                if obj is not None:
                    ext = obj.meta.get("extent_bits", 0)
                    if ext:  # NOT of a never-written string is a no-op
                        self.store.swap(target, engine.bitset_not_masked(
                            obj.state, np.uint32(ext)))
                op.future.set_result(None)
                continue
            obj = self._bitset(target, nbits=1024)
            arrays = (obj.state, *arrays)
            # No existing sources: BITOP with only the destination leaves it
            # unchanged (never wipe the destination).
            if len(arrays) == 1:
                acc = obj.state
            else:
                # One program pads and stacks the operands and folds them.
                with stage("backend.launch", sources=len(arrays)):
                    acc = engine.bitset_bitop(arrays, kind)
            obj.meta["nbits"] = acc.shape[0]
            # BITOP dest width = max of the operands' written extents
            # (redis: STRLEN of the result equals the widest source). A
            # fresh dest defaults to 0 — its pow2 allocation must not leak
            # into size() (review r5).
            obj.meta["extent_bits"] = max(
                [obj.meta.get("extent_bits", 0)]
                + [o.meta.get("extent_bits", 0) for o in src_objs])
            self.store.swap(target, acc)
            self._touch(target)
            op.future.set_result(None)

    # -- Bloom --------------------------------------------------------------

    def _op_bloom_init(self, target: str, ops: List[Op]) -> None:
        """tryInit: create config+bits if absent; False if config exists and
        differs (the reference re-reads config and retries,
        RedissonBloomFilter.java:80-114)."""
        self._check_not_hll(target, ObjectType.BLOOM)
        for op in ops:
            n, p = op.payload["expected_insertions"], op.payload["false_probability"]
            blocked = bool(op.payload.get("blocked"))
            m = bloom_ops.optimal_num_of_bits(n, p)
            k = bloom_ops.optimal_num_of_hash_functions(n, m)
            if blocked:
                m = bloom_ops.blocked_geometry(m)
            bloom_ops.check_size(m)
            existing = self.store.get(target, ObjectType.BLOOM)
            if existing is not None:
                op.future.set_result(False)
                continue
            self.store.get_or_create(
                target,
                ObjectType.BLOOM,
                lambda: bitset_ops.make(m),
                {
                    "size": m,
                    "hash_iterations": k,
                    "expected_insertions": n,
                    "false_probability": p,
                    "blocked": blocked,
                },
            )
            self._touch(target)
            op.future.set_result(True)

    def _bloom_meta(self, target: str):
        self._check_not_hll(target, ObjectType.BLOOM)
        obj = self.store.get(target, ObjectType.BLOOM)
        if obj is None:
            raise RuntimeError(f"bloom filter '{target}' is not initialized")
        return obj, obj.meta["size"], obj.meta["hash_iterations"]

    # -- Bloom host mirror (transfer-adaptive ingest) ------------------------
    #
    # The bloom analogue of the HLL hostfold, shaped by a different constant:
    # an HLL folds into 16 KB, but a filter's bitmap is m/8 bytes (32 MB at
    # m=2^28), so shipping it per run would lose. Instead the filter is
    # DUAL-RESIDENT: a packed host replica ("mirror") absorbs native k-hash
    # folds and serves native membership with ZERO link traffic; the device
    # copy is brought current lazily — one packed OR — only when a
    # device-side op (device-resident probes, BITCOUNT, export/durability)
    # actually needs it. Invariants:
    #   * mirror valid   <=> mir["synced_dev"] == obj.version  (no device
    #     write since the mirror was built/synced);
    #   * device current <=> mir["host_v"] == mir["absorbed_v"] (no host
    #     fold pending absorb).
    # Every device-path bloom op calls _bloom_device_sync first, so at the
    # moment a device write bumps obj.version there are never pending host
    # bits — the two sides never hold disjoint private writes.
    # Classic layout only (the blocked layout's value is device-side gather
    # locality; its wire/host story is the classic filter).

    def _bloom_use_host(self, target: str, obj, nkeys: int) -> bool:
        from redisson_tpu import native as native_mod

        if self.ingest == "device" or obj.meta.get("blocked"):
            return False
        if not native_mod.available():
            return False
        mir = self._bloom_mirrors.get(target)
        if mir is not None and mir["synced_dev"] == obj.version:
            return True  # sticky: a valid mirror keeps serving host ops
        if self.ingest == "hostfold":
            return True
        # Every other ingest value adopts a mirror when the HLL link rule
        # would fold a batch this size on the host.
        return ingest_rule("auto", nkeys, self.store.device) == "hostfold"

    def _bloom_mirror(self, target: str, obj, m: int) -> dict:
        """The current host replica (build/refresh if a device-side write
        invalidated it). A fresh filter (version 0) mirrors as zeros; an
        existing one is packed ON DEVICE and pulled once (1 bit per bit
        over the link, m/8 bytes)."""
        mir = self._bloom_mirrors.get(target)
        if mir is not None and mir["synced_dev"] == obj.version:
            return mir
        if mir is not None and mir["host_v"] != mir["absorbed_v"]:
            # Defensive: pending host bits with an invalidated mirror means
            # some device write path skipped its sync barrier. Push the
            # host bits down first (ORing true bits is always safe), then
            # rebuild from the device, which now holds both sides' writes.
            self._bloom_device_sync(target)
        nbytes = (m + 7) // 8
        if obj.version == 0:
            bits = np.zeros(nbytes, np.uint8)
        else:
            # graftlint: allow-sync(mirror seeding is a one-time snapshot read; callers tolerate the blocking pack)
            bits = np.asarray(engine.bitset_pack(obj.state))[:nbytes].copy()
        mir = {"bits": bits, "synced_dev": obj.version,
               "host_v": 0, "absorbed_v": 0}
        self._bloom_mirrors[target] = mir
        return mir

    def _bloom_device_sync(self, target: str) -> None:
        """Absorb host-pending mirror bits into the device filter (one
        packed upload + OR kernel). Device-side bloom ops and the
        durability/checkpoint barrier (`bloom_sync` op) call this."""
        mir = self._bloom_mirrors.get(target)
        if mir is None or mir["host_v"] == mir["absorbed_v"]:
            return
        import jax

        obj = self.store.get(target, ObjectType.BLOOM)
        was_valid = mir["synced_dev"] == obj.version
        new = engine.bitset_absorb_packed(
            obj.state, jax.device_put(mir["bits"], self.store.device))
        self.store.swap(target, new)
        # The absorb itself adds no logical bits (host writes already bumped
        # the epoch), but replication/restore flows rebuild state through
        # here — invalidate so no pre-absorb read survives (satellite pin).
        self._touch(target)
        mir["absorbed_v"] = mir["host_v"]
        if was_valid:
            mir["synced_dev"] = obj.version  # device == mirror right now
        # else: the mirror was already missing device writes; it stays
        # invalid and the next host-path op rebuilds it from the device.

    def _op_bloom_sync(self, target: str, ops: List[Op]) -> None:
        """Barrier: make the device filter include every host-mirror write
        (no-op when nothing is pending or the name is not a bloom)."""
        if self.store.get(target) is not None:
            self._bloom_device_sync(target)
        for op in ops:
            op.future.set_result(None)

    def _bloom_host_add(self, target: str, obj, m: int, k: int,
                        ops: List[Op]) -> None:
        from redisson_tpu import native as native_mod

        mir = self._bloom_mirror(target, obj, m)
        for op in ops:
            p = op.payload
            if "packed" in p:
                newly = native_mod.bloom_fold_u64(
                    p["packed"], mir["bits"], k, m, self.seed)
            else:
                newly = native_mod.bloom_fold_rows(
                    p["data"], p["lengths"], mir["bits"], k, m, self.seed)
            op.future.set_result(newly.view(np.bool_))  # zero-copy
        mir["host_v"] += 1
        self._touch(target)

    def _bloom_host_contains(self, target: str, obj, m: int, k: int,
                             ops: List[Op], count_only: bool = False,
                             on_result=None) -> None:
        from redisson_tpu import native as native_mod

        mir = self._bloom_mirror(target, obj, m)
        for op in ops:
            p = op.payload
            if "packed" in p:
                hits = native_mod.bloom_contains_u64(
                    p["packed"], mir["bits"], k, m, self.seed)
            else:
                hits = native_mod.bloom_contains_rows(
                    p["data"], p["lengths"], mir["bits"], k, m, self.seed)
            res = int(hits.sum()) if count_only else hits.view(np.bool_)
            if on_result is not None:
                on_result(op, res)
            op.future.set_result(res)

    def _bloom_run(self, target: str, ops: List[Op], mutate: bool,
                   on_result=None) -> None:
        """Shared bloom dispatch: a coalesced run is processed in op order
        (positional result slicing), packed runs coalesce small arrays via
        _segments (order-preserving concat) and chunk like the hll path,
        byte runs coalesce through _coalesce_bytes."""
        obj, m, k = self._bloom_meta(target)
        add_packed, contains_packed, add_bytes, contains_bytes = (
            self._bloom_kernels(obj))
        if mutate and not obj.meta.get("blocked"):
            # Classic-layout adds take the ingest rule's set-bits strategy
            # (scatter, or a forced segment-or); the blocked
            # layout's cache-local scatter stays as-is.
            impl = self._plan_bits(
                sum(op.nkeys or self._payload_nkeys(op) for op in ops))
            add_packed = functools.partial(add_packed, impl=impl)
            add_bytes = functools.partial(add_bytes, impl=impl)
        outs, spans = [], []

        def emit(res, n):
            if mutate:
                new, res = res
                self.store.swap(target, new)
            outs.append(res)
            spans.append(n)

        for fmt, group in _format_runs(ops):
            if fmt == "packed":
                for packed in _segments(
                    [op.payload["packed"] for op in group], engine.MIN_BUCKET
                ):
                    for s, e in engine.chunk_spans(packed.shape[0]):
                        rows, count = engine.pad_rows(packed[s:e])
                        fn = add_packed if mutate else contains_packed
                        emit(fn(obj.state, rows, np.int32(count),
                                k, m, self.seed), e - s)
            else:
                data, lengths, _ = self._coalesce_bytes(group)
                for s, e in engine.chunk_spans(data.shape[0]):
                    pdata, plengths, valid = engine.pad_bytes(
                        data[s:e], lengths[s:e])
                    fn = add_bytes if mutate else contains_bytes
                    emit(fn(obj.state, pdata, plengths, valid,
                            k, m, self.seed), e - s)
        if mutate:
            self._touch(target)
        self.completer.submit(
            self._slice_results(ops, outs, spans, on_result=on_result))

    @staticmethod
    def _bloom_kernels(obj):
        """Kernel set per filter layout (classic vs blocked, see
        ops/bloom.py BLOCK_BITS)."""
        if obj.meta.get("blocked"):
            return (engine.blocked_bloom_add_packed,
                    engine.blocked_bloom_contains_packed,
                    engine.blocked_bloom_add_bytes,
                    engine.blocked_bloom_contains_bytes)
        return (engine.bloom_add_packed, engine.bloom_contains_packed,
                engine.bloom_add_bytes, engine.bloom_contains_bytes)

    def _op_bloom_add(self, target: str, ops: List[Op]) -> None:
        obj, m, k = self._bloom_meta(target)
        nkeys = sum(op.nkeys or self._payload_nkeys(op) for op in ops)
        if self._bloom_use_host(target, obj, nkeys):
            self._bloom_host_add(target, obj, m, k, ops)
            return
        self._bloom_device_sync(target)
        self._bloom_run(target, ops, mutate=True)

    # Probe payloads above this many keys are not memoized — digesting the
    # raw bytes would rival the membership kernel itself.
    _CONTAINS_CACHE_MAX = 4096

    @classmethod
    def _probe_digest(cls, op: Op):
        """Stable fingerprint of a small host probe payload, or None for
        device-resident / oversized payloads (those skip the read cache)."""
        import hashlib

        p = op.payload
        if "device_packed" in p:
            return None
        h = hashlib.blake2b(digest_size=16)
        if "packed" in p:
            arr = p["packed"]
            if arr.shape[0] > cls._CONTAINS_CACHE_MAX:
                return None
            h.update(b"p")
            h.update(np.ascontiguousarray(arr).tobytes())
        else:
            data, lengths = p["data"], p["lengths"]
            if data.shape[0] > cls._CONTAINS_CACHE_MAX:
                return None
            h.update(b"b")
            h.update(np.ascontiguousarray(data).tobytes())
            h.update(np.ascontiguousarray(lengths).tobytes())
        return h.digest()

    def _op_bloom_contains(self, target: str, ops: List[Op]) -> None:
        obj, m, k = self._bloom_meta(target)
        nkeys = sum(op.nkeys or self._payload_nkeys(op) for op in ops)
        use_host = self._bloom_use_host(target, obj, nkeys)
        if not use_host:
            # Sync before the epoch read: absorbing pending host bits bumps
            # the epoch, so the entries filled below stay servable after.
            self._bloom_device_sync(target)
        epoch = self._epoch(target)
        pending: List[Op] = []
        digests = {}
        for op in ops:
            dig = self._probe_digest(op)
            if dig is not None:
                hit = self.read_cache.get(
                    target, "bloom_contains", epoch, extra=dig)
                if self.read_cache.is_hit(hit):
                    # Serve a copy via the completer so per-target resolution
                    # order matches submission order even on a hit.
                    _trace_cache([op], hit=True)
                    self.completer.submit(
                        _complete_all([op], lambda v=hit: v.copy()))
                    continue
                digests[id(op)] = dig
            _trace_cache([op], hit=False)
            pending.append(op)
        if not pending:
            return

        def remember(op: Op, value) -> None:
            dig = digests.get(id(op))
            if dig is not None:
                self.read_cache.put(target, "bloom_contains", epoch,
                                    np.array(value, copy=True), extra=dig)

        if use_host:
            self._bloom_host_contains(target, obj, m, k, pending,
                                      on_result=remember)
            return
        self._bloom_run(target, pending, mutate=False, on_result=remember)

    def _op_bloom_contains_count(self, target: str, ops: List[Op]) -> None:
        """Hit count per op (host-packed or device-resident keys): chunks
        reduce on device, one int32 scalar rides back per op."""
        obj, m, k = self._bloom_meta(target)
        host_ops = [op for op in ops if "device_packed" not in op.payload]
        if host_ops and self._bloom_use_host(
                target, obj,
                sum(op.nkeys or self._payload_nkeys(op) for op in host_ops)):
            self._bloom_host_contains(target, obj, m, k, host_ops,
                                      count_only=True)
            ops = [op for op in ops if "device_packed" in op.payload]
            if not ops:
                return
        self._bloom_device_sync(target)
        count_fn = (engine.blocked_bloom_contains_count_packed
                    if obj.meta.get("blocked")
                    else engine.bloom_contains_count_packed)
        for op in ops:
            parts = []
            if "device_packed" in op.payload:
                arr = op.payload["device_packed"]
                for s, e in engine.chunk_spans(int(arr.shape[0])):
                    chunk = arr[s:e]
                    n = e - s
                    b = engine.bucket_size(n)
                    if n != b:
                        chunk = jnp.zeros((b, 2), jnp.uint32).at[:n].set(chunk)
                    parts.append(count_fn(
                        obj.state, chunk, np.int32(n), k, m, self.seed))
            else:
                packed = op.payload["packed"]
                for s, e in engine.chunk_spans(packed.shape[0]):
                    rows, count = engine.pad_rows(packed[s:e])
                    parts.append(count_fn(
                        obj.state, rows, np.int32(count), k, m, self.seed))
            total = _start_d2h(functools.reduce(jnp.add, parts)) if parts else 0
            self.completer.submit(
                _complete_all([op], lambda t=total: int(t)))

    def _op_bloom_meta(self, target: str, ops: List[Op]) -> None:
        obj, m, k = self._bloom_meta(target)
        meta = dict(obj.meta)
        for op in ops:
            op.future.set_result(meta)

    def _op_bloom_count(self, target: str, ops: List[Op]) -> None:
        from redisson_tpu import native as native_mod

        obj, m, k = self._bloom_meta(target)
        mir = self._bloom_mirrors.get(target)
        use_mirror = mir is not None and mir["synced_dev"] == obj.version
        if not use_mirror:
            # Sync first: it may bump the epoch (absorb), and the cache fill
            # below must be stamped with the post-absorb epoch to be useful.
            self._bloom_device_sync(target)
        epoch = self._epoch(target)
        cached = self.read_cache.get(target, "bloom_count", epoch)
        if self.read_cache.is_hit(cached):
            _trace_cache(ops, hit=True)
            for op in ops:
                op.future.set_result(cached)
            return
        _trace_cache(ops, hit=False)
        if use_mirror:
            # Valid mirror holds every bit: host popcount, zero link traffic.
            bc = native_mod.popcount(mir["bits"])
        else:
            # graftlint: allow-sync(mirror-miss fallback: count() is a synchronous API and must block on the fresh BITCOUNT)
            bc = int(engine.bitset_cardinality(obj.state))
        # bc is a host int here — the pure-math estimate matches the wire
        # tier (interop/bloom_redis) bit-for-bit and avoids a device call.
        est = bloom_math.count_estimate(bc, m, k)
        val = int(round(est))
        self.read_cache.put(target, "bloom_count", epoch, val)
        for op in ops:
            op.future.set_result(val)

    def _op_bits_export(self, target: str, ops: List[Op]) -> None:
        """(otype, host cells, meta, version) for a bitset/bloom — the
        generic checkpoint/durability read (pod mode's sharded twin trims
        its shard padding; here the array is already logical-length)."""
        obj = self.store.get(target)
        if obj is None or obj.otype not in (ObjectType.BITSET, ObjectType.BLOOM):
            for op in ops:
                op.future.set_result(None)
            return
        if obj.otype == ObjectType.BLOOM:
            self._bloom_device_sync(target)
            obj = self.store.get(target)
        host = np.asarray(obj.state).astype(np.uint8)
        for op in ops:
            op.future.set_result((obj.otype, host, dict(obj.meta), obj.version))

    def _op_bits_import(self, target: str, ops: List[Op]) -> None:
        """Create/overwrite a store bitset/bloom from host cells (the
        checkpoint-restore path; pod checkpoints restore into the
        single-chip tier through this — portability both ways)."""
        import jax

        for op in ops:
            otype = op.payload["otype"]
            host = np.asarray(op.payload["array"]).astype(np.uint8)
            meta = dict(op.payload.get("meta") or {})
            self._check_not_hll(target, otype)
            arr = jax.device_put(host, self.store.device)
            if otype == ObjectType.BITSET:
                meta.setdefault("nbits", host.shape[0])
                meta.setdefault("extent_bits", host.shape[0])
            obj = self.store.get_or_create(target, otype, lambda: arr, meta)
            self.store.swap(target, arr)
            obj.meta.update(meta)
            self._bloom_mirrors.pop(target, None)
            # Checkpoint restore replaces the whole object: epoch bump so
            # no pre-restore read survives in the cache.
            self._touch(target)
            op.future.set_result(True)

    # -- generic ------------------------------------------------------------

    def _op_delete(self, target: str, ops: List[Op]) -> None:
        row = self._alloc.release(target)
        if row is not None:
            self.bank = engine.hll_bank_zero_row(self.bank, np.int32(row))
            res = True
        else:
            self._bloom_mirrors.pop(target, None)
            res = self.store.delete(target)
        self._touch(target)
        self.read_cache.invalidate(target)
        for op in ops:
            op.future.set_result(res)

    def _op_exists(self, target: str, ops: List[Op]) -> None:
        res = target in self._rows or self.store.exists(target)
        for op in ops:
            op.future.set_result(res)

    def _op_rename(self, target: str, ops: List[Op]) -> None:
        """RENAME/RENAMENX for sketch-tier objects (bank HLL rows move by
        remapping; store objects re-key; bloom mirrors follow). Atomic: the
        whole check+move runs on the dispatcher."""
        for op in ops:
            new = op.payload["newkey"]
            # Redis RENAME/RENAMENX errors on a missing source regardless of
            # the destination, and must leave the destination intact — so the
            # source check comes first, before any destructive step, and a
            # failure is per-op (doesn't abort coalesced siblings).
            if target not in self._rows and not self.store.exists(target):
                op.future.set_exception(KeyError(f"no such key '{target}'"))
                continue
            if op.payload.get("nx") and (
                    new in self._rows or self.store.exists(new)):
                op.future.set_result(False)
                continue
            # RENAME overwrites the destination in this tier.
            row = self._alloc.release(new)
            if row is not None:
                self.bank = engine.hll_bank_zero_row(self.bank, np.int32(row))
            self.store.delete(new)
            self._bloom_mirrors.pop(new, None)
            if target in self._rows:
                self._alloc.rows[new] = self._alloc.rows.pop(target)
                self._alloc.versions[new] = (
                    self._alloc.versions.pop(target, 0) + 1)
            else:
                self.store.rename(target, new)
                mir = self._bloom_mirrors.pop(target, None)
                if mir is not None:
                    self._bloom_mirrors[new] = mir
            self._touch(target)
            self._touch(new)
            self.read_cache.invalidate(target)
            self.read_cache.invalidate(new)
            op.future.set_result(True)

    def _op_flushall(self, target: str, ops: List[Op]) -> None:
        # Runs on the dispatcher thread, so it is serialized against every
        # other op (no mid-kernel store mutation). The bank is dropped, not
        # zeroed — lazily reallocated on the next HLL touch.
        self._alloc.clear()
        self.bank = None
        self._bloom_mirrors.clear()
        self._epochs.clear()
        self.read_cache.clear()
        self.store.flushall()
        self._account_bank()
        for op in ops:
            op.future.set_result(None)

    # -- geo remote apply (cross-site replication, geo/) --------------------

    def _op_geo_merge(self, target: str, ops: List[Op]) -> None:
        """Classic fallback absorb for remote delta planes — non-delta
        ingest configs and targets the delta gate rejected (blocked
        blooms, WRONGTYPE probes). Merges each plane into local state on
        the host; blocks the dispatcher on a D2H readback, so the fused
        _geo_fold_group path is the hot path."""
        self.counters["geo_classic"] += len(ops)
        for op in ops:
            try:
                self._geo_merge_one(target, op.payload)
            except Exception as exc:  # noqa: BLE001 — per-op isolation
                op.future.set_exception(classify(exc, seam="kernel_launch"))
                continue
            op.future.set_result(True)

    def _geo_merge_one(self, target: str, payload: dict) -> None:
        import jax

        inner = payload["inner"]
        plane = self._geo_plane(payload)
        if inner == "hll_add":
            row = self._hll_row(target)  # WRONGTYPE if a store object
            # graftlint: allow-sync(classic geo fallback — deliberately blocks the dispatcher on the readback; the fused _geo_fold_group path is the hot path and never lands here)
            cur = np.asarray(
                engine.hll_bank_row(self._ensure_bank(), np.int32(row)))
            regs = np.maximum(cur.astype(np.uint8), plane).astype(np.int32)
            self.bank = engine.hll_bank_set_row(
                self.bank, self._put(regs), np.int32(row))
            self._bump(target)
            return
        if inner == "bloom_add":
            obj = self._geo_bloom_ensure(target, payload.get("meta") or {})
            m = obj.meta["size"]
            if int(payload["cells"]) != m:
                raise RuntimeError(
                    f"geo bloom plane for '{target}' sized "
                    f"{payload['cells']} bits vs local filter {m}")
            self._bloom_device_sync(target)
            obj = self.store.get(target, ObjectType.BLOOM)
            merged = np.asarray(obj.state).astype(np.uint8)
            cells = np.unpackbits(plane)[:m]
            np.maximum(merged, cells, out=merged)
            self.store.swap(
                target, jax.device_put(merged, self.store.device))
            self._bloom_mirrors.pop(target, None)
            self._touch(target)
            return
        # bitset_set
        obj = self._bitset(target, nbits=1024)
        cells = int(payload["cells"])
        obj = self._grow_for(obj, max(cells - 1, 0))
        ext = int((payload.get("meta") or {}).get("max_idx", -1))
        if ext >= 0:
            self._extend(obj, ext)
        merged = np.asarray(obj.state).astype(np.uint8)
        unp = np.unpackbits(plane)
        n = min(unp.shape[0], merged.shape[0])
        np.maximum(merged[:n], unp[:n], out=merged[:n])
        self.store.swap(target, jax.device_put(merged, self.store.device))
        self._touch(target)

    def _op_geo_replace(self, target: str, ops: List[Op]) -> None:
        """Stamped full-state overwrite — the LWW half of the geo contract
        (bitset clears, tombstone resurrections, anti-entropy snapshot
        repair). The applier (geo/applier.py) decides WHETHER the stamp
        wins before dispatching; this handler only installs the state."""
        import jax

        for op in ops:
            try:
                payload = op.payload
                inner = payload["inner"]
                plane = self._geo_plane(payload)
                if inner == "hll_add":
                    row = self._hll_row(target)
                    self.bank = engine.hll_bank_set_row(
                        self._ensure_bank(),
                        self._put(plane.astype(np.int32)),
                        np.int32(row))
                    self._bump(target)
                else:
                    otype = (ObjectType.BLOOM if inner == "bloom_add"
                             else ObjectType.BITSET)
                    self._check_not_hll(target, otype)
                    cells = int(payload["cells"])
                    host = np.unpackbits(plane)[:cells].astype(np.uint8)
                    meta = dict(payload.get("meta") or {})
                    arr = jax.device_put(host, self.store.device)
                    if otype == ObjectType.BITSET:
                        meta.setdefault("nbits", cells)
                        meta.pop("max_idx", None)
                        meta.setdefault("extent_bits", cells)
                    obj = self.store.get_or_create(
                        target, otype, lambda: arr, meta)
                    if obj.otype != otype:
                        raise WrongTypeError(
                            f"key '{target}' holds {obj.otype}, geo "
                            f"replace carries {otype}")
                    self.store.swap(target, arr)
                    obj.meta.update(meta)
                    self._bloom_mirrors.pop(target, None)
                    self._touch(target)
                self.read_cache.invalidate(target)
            except Exception as exc:  # noqa: BLE001 — per-op isolation
                op.future.set_exception(classify(exc, seam="kernel_launch"))
                continue
            op.future.set_result(True)

    def _op_geo_delete(self, target: str, ops: List[Op]) -> None:
        """Stamped tombstone delete: state-wise identical to _op_delete;
        the (origin_seq, site) stamp in the payload exists for the journal
        (crash replay) and the applier's LWW bookkeeping."""
        self._op_delete(target, ops)

    def _op_geo_flush(self, target: str, ops: List[Op]) -> None:
        """Stamped keyspace flush: deletes the CONCRETE key list the
        applier resolved against its LWW floors (keys with writes newer
        than the flush stamp survive) — replay-deterministic, unlike
        re-enumerating the keyspace at recovery time."""
        for op in ops:
            wiped = 0
            for name in op.payload.get("keys", ()):
                row = self._alloc.release(name)
                if row is not None:
                    self.bank = engine.hll_bank_zero_row(
                        self.bank, np.int32(row))
                    wiped += 1
                else:
                    self._bloom_mirrors.pop(name, None)
                    if self.store.delete(name):
                        wiped += 1
                self._touch(name)
                self.read_cache.invalidate(name)
            op.future.set_result(wiped)
