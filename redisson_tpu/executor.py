"""L2 — the command executor: the single narrow waist of the framework.

Mirrors the reference's `CommandExecutor` seam (`command/CommandExecutor.java`
= CommandSyncExecutor + CommandAsyncExecutor; the universal entry point is
`CommandAsyncService.async()`, `command/CommandAsyncService.java:378`). Every
object operation flows through `execute_async()` here; swapping the backend
(TPU engine / in-memory local / real Redis) happens below this line and the
object API never notices — exactly the plugin boundary the north star
prescribes.

Dispatch model (the TPU analogue of the reference's pipelining):
  * every op is enqueued to its target object's FIFO queue (per-object order
    = the reference's per-connection `CommandsQueue` ordering guarantee);
  * a single dispatcher thread (the "event loop") drains queues, coalescing
    consecutive same-kind key-batch ops on one object into a single padded
    device call (`CommandBatchService`-style batching, but implicit);
  * dispatch is a three-stage pipeline (the reference keeps N commands in
    flight per connection through the Netty channel + `CommandsQueue`; we
    keep N *runs* in flight against the device): the dispatcher only STAGES
    a run (pad + device_put + enqueue the jitted call — `backend.run`
    returns without blocking on results), a bounded in-flight window
    (`inflight_runs`, default 2) keeps the device busy, and the backend's
    completer thread resolves futures as device results land. Per-target
    serialization is preserved by never admitting a second run for a target
    (or a GLOBAL_COALESCE kind) whose predecessor hasn't completed;
    backends that commit all observable state at stage time (dispatch-time
    state — they set `DISPATCH_TIME_STATE = True`) release that gate as
    soon as `run()` returns, so only the window bounds their depth;
  * batching decisions are delegated to a policy object: the default
    `GreedyBatchPolicy` reproduces the seed behavior (drain until the key
    cap, never wait); the serving layer installs
    `serve.policy.AdaptiveBatchPolicy`, which sizes batches from an online
    cost model and holds a batch open up to min(deadline slack, max_linger)
    so small-op tenants are not starved by bulk ingest;
  * ops may carry an absolute `deadline`; expired ops complete with
    `DeadlineExceeded` *before* device dispatch (they never reach
    `backend.run`), so a caller's latency budget bounds queueing, not just
    service;
  * results complete `concurrent.futures.Future`s in submission order per
    object; `execute_sync` blocks on the future like the reference's sync
    facade blocks on its latch (`CommandAsyncService.java:86-105`).

Batch-visibility semantics (documented deviation): per-key "changed/added"
results of a coalesced batch are evaluated against the object state at batch
start, not per preceding key. The reference runs per-command and observes
every intermediate state; at 100M+ keys/sec the intermediate states are not
individually materialized. Tests pin this contract.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from redisson_tpu import contractwitness as _cw
from redisson_tpu.concurrency import make_condition, make_lock
from redisson_tpu.fault import inject as fault_inject
from redisson_tpu.fault.taxonomy import StateUncertainFault, classify
from redisson_tpu.serve.errors import DeadlineExceeded
from redisson_tpu.trace.stages import stage

# graftlint Tier C guarded-by audit (tools/graftlint/concurrency.py):
# which shared attribute is protected by which lock. `token.*` entries use
# name-based provenance — any `token.<attr>` access must hold
# `with token.lock:` (the per-run completion token is touched by every
# completer thread racing its siblings and the dispatcher).
GUARDED_BY = {
    "CommandExecutor._queues": "_lock",
    "CommandExecutor._ready": "_lock",
    "CommandExecutor._inflight": "_lock",
    "CommandExecutor._inflight_targets": "_lock",
    "CommandExecutor._inflight_kinds": "_lock",
    "CommandExecutor._staging_bytes": "_lock",
    "CommandExecutor._runs_completed": "_lock",
    "CommandExecutor._runs_overlapped": "_lock",
    "CommandExecutor._shutdown": "_lock",
    "CommandExecutor._journal": "_lock:writes",
    "CommandExecutor._trace": "_lock:writes",
    "CommandExecutor._stage_ns":
        "racy:monotonic counter written only in _dispatch, torn read ok",
    "CommandExecutor._runs_staged":
        "racy:monotonic counter written only in _dispatch, torn read ok",
    "token.pending": "lock",
    "token.op_failed": "lock:writes",
    "token.fault_exc": "lock:writes",
}

# Op kinds that may coalesce with the previous op of the same kind+target.
COALESCABLE = {"hll_add", "bloom_add", "bitset_set", "bitset_clear", "bitset_get", "bloom_contains"}

# Kinds whose futures stay pending until a LATER op (a push serving the
# parked waiter, or bpop_cancel) or a client-side timeout fulfils them.
# Such a run must release its target gate at run() return and never occupy
# an in-flight window slot: holding either would gate the very op that
# fulfils it — two parked pops would wedge the whole window. These runs
# keep the seed's dispatch semantics (the reference parks its timeoutless
# blocking commands on a dedicated connection OUTSIDE the pipeline for the
# same reason, `command/CommandAsyncService.java:491-497`).
PARKED_KINDS = frozenset({"bpop"})

# Pseudo-kind intercepted by the dispatcher itself: the op's payload is a
# zero-arg callable executed inline on the dispatcher thread. Because the
# dispatcher is the only thread that stages runs AND appends journal
# records, a barrier is an exact consistency cut for dispatch-time-state
# backends — every previously dispatched run's state is committed and its
# journal records appended, and nothing new stages while the callable
# runs. The persist snapshotter cuts its snapshots through this.
BARRIER_KIND = "__barrier__"

_op_counter = itertools.count()


@dataclass
class Op:
    """One queued operation (the analogue of CommandData)."""

    target: str  # object name ("" for global ops)
    kind: str
    payload: Any
    future: Future = field(default_factory=Future)
    index: int = field(default_factory=lambda: next(_op_counter))
    nkeys: int = 0  # number of key lanes this op contributed (for slicing)
    tenant: str = ""  # admission identity ("" = the default tenant)
    deadline: Optional[float] = None  # absolute executor-clock time, or None
    enqueued_at: float = 0.0  # executor-clock time of enqueue (QoS delay)
    # Sampled trace span (trace/spans.py) or None. None for the vast
    # majority of ops at the default sampling stride; every stamp below
    # guards on it so disabled tracing costs one attribute read.
    span: Any = None
    # Logical cluster shard this op was dispatched FOR (mesh data plane:
    # N logical shards share one executor, and the ownership guard at the
    # backend waist compares this tag against the authoritative slot
    # owner to generate MOVED exactly like the per-stack guards do).
    # -1 = untagged (single-engine modes and the stacks data plane).
    shard: int = -1


def _op_payload_nbytes(op: Op) -> int:
    """Best-effort payload byte size for the staging meter: arrays report
    nbytes (metadata read, no sync); dict payloads sum their array
    members; everything else (scalars, callables) is uncounted."""
    p = op.payload
    nb = getattr(p, "nbytes", None)
    if nb is not None:
        try:
            return int(nb)
        except (TypeError, ValueError):
            return 0
    if isinstance(p, dict):
        total = 0
        for v in p.values():
            vnb = getattr(v, "nbytes", None)
            if vnb is not None:
                try:
                    total += int(vnb)
                except (TypeError, ValueError):
                    pass
        return total
    return 0


class GreedyBatchPolicy:
    """The seed dispatch behavior as a policy object: drain whatever is
    queued up to the key cap, never hold a batch open. The serving layer
    swaps in `serve.policy.AdaptiveBatchPolicy`; everything else runs this.
    """

    def batch_key_limit(self, kind: str, default_cap: int) -> int:
        return default_cap

    def linger_s(self, kind: str, keys: int, cap: int,
                 run: Sequence[Op], now: float) -> float:
        return 0.0

    def observe(self, kind: str, nkeys: int, seconds: float) -> None:
        pass

    def snapshot(self) -> Dict[str, Any]:
        return {"policy": "greedy"}


class _InflightRun:
    """Bookkeeping for one dispatched run, alive until its last op future
    resolves (the executor-side analogue of one entry in the reference's
    per-connection `CommandsQueue`)."""

    __slots__ = ("kind", "target", "targets", "is_global", "nops", "nkeys",
                 "t0", "queue_delay_s", "stage_s", "pending", "failed",
                 "op_failed", "overlapped", "depth", "gates_held", "lock",
                 "ops", "fault_exc", "run_span", "staged_bytes")

    def __init__(self, kind: str, target: str, targets: frozenset,
                 is_global: bool):
        self.kind = kind
        self.target = target
        self.targets = targets
        self.is_global = is_global
        self.nops = 0
        self.nkeys = 0
        self.t0 = 0.0
        self.queue_delay_s = 0.0
        self.stage_s = None
        self.pending = 0
        self.failed = False
        self.op_failed = False
        self.overlapped = False
        self.depth = 1
        self.gates_held = True
        self.lock = make_lock("executor._InflightRun.lock")
        self.ops: Sequence[Op] = ()  # live ops (watchdog trip / diagnostics)
        self.fault_exc = None  # first StateUncertainFault among the ops
        self.run_span = None  # parent trace span for this pipeline window
        self.staged_bytes = 0  # payload bytes charged to the staging meter


class CommandExecutor:
    """The async executor around a backend's op handlers.

    backend must expose `run(kind, target, ops: List[Op]) -> None`, completing
    each op's future. Coalescable kinds receive the whole run of consecutive
    same-kind ops; others receive singletons.
    """

    # Cluster tier: which shard this executor serves (set by the client for
    # shard members; None = unsharded). Surfaces through pipeline_stats so
    # per-shard dispatch work is attributable in rollups and traces.
    shard_tag: Optional[int] = None

    def __init__(self, backend, max_batch_keys: int = 1 << 21, metrics=None,
                 policy=None, clock: Callable[[], float] = None,
                 inflight_runs: int = 2, journal=None, trace=None):
        self._backend = backend
        # Trace subsystem (trace/manager.py TraceManager) or None. The
        # manager must share this executor's clock so span timestamps and
        # deadlines live on one timeline.
        self._trace = trace
        # Write-ahead op journal (persist/journal.py) or None. Appended on
        # the dispatcher thread before each run stages; installed late by
        # the client (after recovery replay) via set_journal().
        self._journal = journal
        self._max_batch_keys = max_batch_keys
        self._metrics = metrics  # ExecutorMetrics or None (zero-cost when off)
        self._policy = policy or GreedyBatchPolicy()
        self._clock = clock or time.monotonic
        # Kinds the backend coalesces across *different* targets (e.g. the
        # pod backend's bank insert, where the device call carries a per-key
        # target row). Per-target FIFO is preserved: only queue heads join.
        self._global_kinds = frozenset(getattr(backend, "GLOBAL_COALESCE", ()))
        # Optional kind -> group aliasing for the cross-target steal: kinds
        # sharing a group value coalesce into ONE run (the TPU backend's
        # delta window stacks hll_add/bloom_add/bitset_set planes into a
        # single fused merge launch). Ungrouped kinds gate under their own
        # name, which reproduces the plain same-kind steal.
        self._coalesce_groups = dict(getattr(backend, "COALESCE_GROUPS", {}))
        # -- pipeline state (tentpole PR 4) --------------------------------
        # A run stays "in flight" from dispatch until its last future
        # resolves; the window bounds how many such runs may exist at once.
        # Backends that commit observable state inside run() (dispatch-time
        # state) let the per-target/per-kind gates release at stage time.
        self._window = max(1, int(inflight_runs))
        self._eager_release = bool(getattr(backend, "DISPATCH_TIME_STATE", False))
        self._inflight: set = set()  # _InflightRun tokens
        self._inflight_targets: set = set()  # gated object names
        self._inflight_kinds: set = set()  # gated GLOBAL_COALESCE kinds
        self._staging_bytes = 0  # in-flight payload bytes (memstat meter)
        self._runs_completed = 0
        self._runs_overlapped = 0
        # Host staging time of every run (token.stage_s), always on.
        self._stage_ns = 0
        self._runs_staged = 0
        self._lock = make_lock("executor.CommandExecutor._lock")
        self._cv = make_condition("executor.CommandExecutor._lock",
                                  self._lock)
        self._queues: Dict[str, deque] = {}
        self._ready: deque = deque()  # round-robin of object names with work
        self._shutdown = False
        # Fault subsystem hooks (fault/manager.py installs; None = off):
        # fault_guard(kind, target) -> Optional[Exception] runs at enqueue
        # time (quarantine/degraded write rejection); fault_listener(kind,
        # targets, fault) fires when a run retires with StateUncertainFault
        # (the rebuild coordinator's trigger).
        self.fault_guard = None
        self.fault_listener = None
        self._thread = threading.Thread(
            target=self._loop, name="redisson-tpu-dispatcher", daemon=True
        )
        self._thread.start()

    @property
    def backend(self):
        """The backend behind this executor — models use it for tier
        capability introspection (e.g. BLOOM_STRICT_MOD)."""
        return self._backend

    @property
    def policy(self):
        """The live batch policy (greedy unless the serving layer installed
        an adaptive one)."""
        return self._policy

    @property
    def journal(self):
        """The attached write-ahead journal, or None (journaling off)."""
        return self._journal

    @property
    def trace(self):
        """The attached TraceManager, or None (tracing off)."""
        return self._trace

    def set_trace(self, trace) -> None:
        """Attach/detach the trace manager; lock-ordered with enqueue so
        no op is half-stamped across the transition."""
        with self._cv:
            self._trace = trace

    def set_journal(self, journal) -> None:
        """Attach/detach the write-ahead journal. The client installs it
        AFTER recovery replay (replayed ops must not re-journal) and
        detaches before close; the swap is lock-ordered with dispatch so
        no run straddles the transition."""
        with self._cv:
            self._journal = journal

    # -- submission ---------------------------------------------------------

    def execute_async(self, target: str, kind: str, payload: Any,
                      nkeys: int = 0, tenant: str = "",
                      deadline: Optional[float] = None,
                      shard: int = -1) -> Future:
        op = Op(target=target, kind=kind, payload=payload, nkeys=nkeys,
                tenant=tenant, deadline=deadline, shard=shard)
        # Contract-witness tap at the single enqueue funnel: every real op
        # kind passes here regardless of surface (facade, wire window,
        # journal replay, replica stream, geo apply).
        if _cw.RECORD is not None and kind != BARRIER_KIND:
            _cw.RECORD(kind)
        with self._cv:
            self._enqueue_locked(op)
            self._cv.notify()
        return op.future

    def execute_many(self, staged: Sequence[Tuple[str, str, Any, int]],
                     tenant: str = "",
                     deadline: Optional[float] = None,
                     admitted_ats: Optional[Sequence[float]] = None,
                     shard: int = -1
                     ) -> List[Future]:
        """Enqueue a pre-staged op list under ONE lock acquisition (the
        RBatch dispatch path): per-target FIFO order follows list order, and
        the whole batch shares one tenant + deadline budget.

        `admitted_ats` (optional, parallel to `staged`) carries upstream
        admission stamps — the wire tier stamps each command at socket
        read, so a sampled span's admission stage covers network queueing
        too. Threaded per-op through the tracer's same-thread handoff."""
        ops = [Op(target=t, kind=k, payload=p, nkeys=n, tenant=tenant,
                  deadline=deadline, shard=shard) for (t, k, p, n) in staged]
        if _cw.RECORD is not None:
            for op in ops:
                if op.kind != BARRIER_KIND:
                    _cw.RECORD(op.kind)
        trace = self._trace
        annotate = (trace.tracer.annotate_next
                    if trace is not None and admitted_ats is not None
                    else None)
        with self._cv:
            for i, op in enumerate(ops):
                if annotate is not None and op.kind != BARRIER_KIND:
                    annotate(admitted_at=admitted_ats[i])
                self._enqueue_locked(op)
            self._cv.notify()
        return [op.future for op in ops]

    def _enqueue_locked(self, op: Op) -> None:
        if self._shutdown:
            # Drain-then-reject: ops already queued at shutdown() still
            # run, but a submission racing shutdown gets a *failed
            # future* — raising here would surface as an unhandled
            # exception in whatever background thread submitted (the
            # reference's shutdown latch rejects the same way,
            # `MasterSlaveConnectionManager.java:651-662`).
            op.future.set_exception(RuntimeError("executor is shut down"))
            return
        guard = self.fault_guard
        if guard is not None:
            # Quarantined/degraded target rejection (set lookups only —
            # the guard must stay cheap under the executor lock).
            exc = guard(op.kind, op.target)
            if exc is not None:
                op.future.set_exception(exc)
                return
        q = self._queues.get(op.target)
        if q is None:
            q = self._queues[op.target] = deque()
        if not q:
            self._ready.append(op.target)
        op.enqueued_at = self._clock()
        trace = self._trace
        if trace is not None and op.kind != BARRIER_KIND:
            # Sampling decision + "queued" stamp; begin_op returns None for
            # the unsampled majority (one counter stride per op).
            op.span = trace.begin_op(op.kind, op.target, op.tenant, op.nkeys)
        q.append(op)

    def execute_sync(self, target: str, kind: str, payload: Any,
                     nkeys: int = 0, shard: int = -1):
        # graftlint: allow-g006(sync facade: blocks exactly like the reference's CommandSyncExecutor latch; serve-mode callers get deadline-bounded waits via the serving layer)
        return self.execute_async(target, kind, payload, nkeys,
                                  shard=shard).result()

    def execute_barrier(self, fn: Callable[[], Any], target: str = "") -> Future:
        """Run `fn` inline on the dispatcher thread, ordered like an op on
        `target`; the future resolves with fn's return value. See
        BARRIER_KIND for the consistency-cut contract."""
        return self.execute_async(target, BARRIER_KIND, fn)

    def queue_depth(self) -> int:
        """Total ops waiting across all object queues (locked snapshot)."""
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    # -- dispatcher ---------------------------------------------------------

    def _loop(self):
        try:
            while True:
                with self._cv:
                    while True:
                        if self._shutdown and not self._ready:
                            return
                        picked = None
                        if self._ready and len(self._inflight) < self._window:
                            picked = self._pick_target_locked()
                        if picked is not None:
                            break
                        # Woken by: a new enqueue, a run completion freeing
                        # a gate or a window slot, or shutdown().
                        self._cv.wait()
                    kind, target, run = self._collect_run_locked(picked)
                    token = self._admit_locked(kind, target, run)
                with stage("executor.dispatch", ops=len(run)):
                    self._dispatch(token, run)
        finally:
            # The dispatcher is the only thread that resolves queued ops; if
            # it exits for ANY reason (clean shutdown drain or an unexpected
            # BaseException), sweep whatever is still queued so no waiter
            # blocks forever on a future nobody will complete.
            self._cancel_remaining()

    def _pick_target_locked(self) -> Optional[str]:
        """First round-robin target whose queue head is admissible: no
        in-flight predecessor holds its target gate (or its kind gate, for
        GLOBAL_COALESCE kinds). Skipping a gated target instead of blocking
        on it is what lets independent targets overlap while per-target FIFO
        stays intact. Removes the pick from the round-robin."""
        for target in self._ready:
            if target in self._inflight_targets:
                continue
            head_kind = self._queues[target][0].kind
            if (head_kind in self._global_kinds
                    and self._group_of(head_kind) in self._inflight_kinds):
                continue
            self._ready.remove(target)
            return target
        return None

    def _group_of(self, kind: str) -> str:
        """Gate/steal key for a global kind: its COALESCE_GROUPS alias, or
        itself when ungrouped."""
        return self._coalesce_groups.get(kind, kind)

    def _admit_locked(self, kind: str, target: str,
                      run: List[Op]) -> _InflightRun:
        """Mark the run in flight: hold its target gate(s) — a global steal
        spans many targets — and, for global kinds, the kind gate."""
        is_global = kind in self._global_kinds
        targets = frozenset({op.target for op in run} | {target})
        token = _InflightRun(kind, target, targets, is_global)
        token.overlapped = bool(self._inflight)
        self._inflight.add(token)
        token.depth = len(self._inflight)
        self._inflight_targets |= targets
        if is_global:
            self._inflight_kinds.add(self._group_of(kind))
        return token

    def _collect_run_locked(self, target: str) -> Tuple[str, str, List[Op]]:
        """Pop the next run: per-target coalesce + policy linger + the
        cross-target steal for global kinds. Caller holds the lock and has
        already removed `target` from the round-robin."""
        q = self._queues[target]
        run = [q.popleft()]
        kind = run[0].kind
        cap = min(self._max_batch_keys,
                  max(run[0].nkeys,
                      int(self._policy.batch_key_limit(kind, self._max_batch_keys))))
        keys = run[0].nkeys
        if kind in COALESCABLE:
            keys = self._drain_same_kind(q, kind, run, keys, cap)
            # Adaptive linger: the policy may hold the batch open for late
            # arrivals (deadline-slack-bounded). cv.wait releases the lock,
            # so submitters keep appending; every wake re-drains. Greedy
            # returns 0.0 and this loop never waits.
            while not self._shutdown and keys < cap:
                wait_s = self._policy.linger_s(kind, keys, cap, run, self._clock())
                if wait_s <= 0.0:
                    break
                self._cv.wait(wait_s)
                keys = self._drain_same_kind(q, kind, run, keys, cap)
        if kind in self._global_kinds:
            keys = sum(op.nkeys for op in run)
            group = self._group_of(kind)
            # Steal queue heads of the same gate group (same kind unless the
            # backend aliases kinds together, e.g. the delta window) from
            # other targets. Mutate _ready/_queues only AFTER the scan —
            # removing entries while walking a snapshot of the round-robin is
            # how targets get dropped (satellite regression: test_serve.py
            # interleave test).
            emptied: List[str] = []
            for other in list(self._ready):
                if keys >= cap:
                    break
                if other == target:
                    # A linger-time submitter can re-add `target` itself to
                    # the round-robin; its queue is the tail logic's problem.
                    continue
                if other in self._inflight_targets:
                    # That target already has a run in flight; stealing its
                    # head would put a second run for it in flight and break
                    # per-target completion ordering.
                    continue
                oq = self._queues[other]
                while (
                    oq
                    and oq[0].kind in self._global_kinds
                    and self._group_of(oq[0].kind) == group
                    and keys + oq[0].nkeys <= cap
                ):
                    op = oq.popleft()
                    keys += op.nkeys
                    if op.span is not None:
                        op.span.event("stolen")
                    run.append(op)
                if not oq:
                    emptied.append(other)
            for other in emptied:
                self._ready.remove(other)
                del self._queues[other]
        # The linger wait releases the lock, so a submitter who found the
        # drained queue empty has re-added `target` to the round-robin —
        # possibly MORE THAN ONCE: each wait/re-drain cycle empties the
        # queue again, and the next refill appends another copy. Strip
        # every copy, then re-add exactly one iff work remains; a single
        # leftover duplicate would outlive the `del` below as a stale
        # round-robin entry and KeyError the dispatcher on its next pick.
        while target in self._ready:
            self._ready.remove(target)
        if q:
            self._ready.append(target)
        else:
            del self._queues[target]
        return kind, target, run

    @staticmethod
    def _drain_same_kind(q: deque, kind: str, run: List[Op], keys: int,
                         cap: int) -> int:
        while q and q[0].kind == kind and keys + q[0].nkeys <= cap:
            op = q.popleft()
            keys += op.nkeys
            run.append(op)
        return keys

    def _dispatch(self, token: _InflightRun, run: List[Op]) -> None:
        """Stage one run: deadline-filter, call backend.run (stage + device
        enqueue; non-blocking for device backends), then let the completion
        callbacks — fired from the backend's completer thread as results
        land, or inline for synchronous backends — retire the run. The
        dispatcher never blocks on results here."""
        m = self._metrics
        kind, target = token.kind, token.target
        now = self._clock()
        # Deadline propagation: expired ops complete with DeadlineExceeded
        # and NEVER reach backend.run — by this point the op has already
        # missed its budget, so burning device time on it only delays the
        # ops behind it (the reference's response-timeout fires the same
        # way, before a retry re-sends).
        live: List[Op] = []
        n_expired = 0
        for op in run:
            if op.deadline is not None and op.deadline <= now:
                n_expired += 1
                if not op.future.done():
                    op.future.set_exception(DeadlineExceeded(
                        f"op {kind}@{op.target or target}: deadline passed "
                        f"{now - op.deadline:.6f}s before dispatch"))
                if op.span is not None:
                    # Pre-dispatch expiry never attaches a done-callback,
                    # so the span must be finished here or it leaks.
                    op.span.event("expired", now)
                    op.span.finish(error="DeadlineExceeded")
            else:
                live.append(op)
        if n_expired and m:
            m.record_expired(kind, n_expired)
        if not live:
            self._retire(token, completed=False)
            return
        if kind == BARRIER_KIND:
            # Consistency cut: executes here, on the dispatcher, with no
            # run staging concurrently. Never touches the backend or the
            # journal and never counts toward batch metrics.
            for op in live:
                try:
                    op.future.set_result(op.payload())
                except Exception as exc:
                    # Barrier callables (snapshot cuts, state swaps) can
                    # fail on device/IO errors too — classify so the fault
                    # counters and any retry wrapper see a decision.
                    op.future.set_exception(classify(exc, seam="snapshot_io"))
            self._retire(token, completed=False)
            return
        token.nops = len(live)
        token.nkeys = sum(op.nkeys for op in live)
        token.ops = live
        # Staging meter (memstat): payload bytes held host-side while this
        # run is in flight; released at retire. nbytes reads are
        # aval/host-array metadata — no device sync on the hot path.
        staged = sum(_op_payload_nbytes(op) for op in live)
        if staged:
            token.staged_bytes = staged
            with self._cv:
                self._staging_bytes += staged
        t0 = token.t0 = self._clock()
        token.queue_delay_s = t0 - min(op.enqueued_at for op in live)
        if m:
            m.record_queue_waits(live, t0)
        # graftlint: allow-guarded(pre-publish init: done-callbacks that contend on token.lock are not armed yet)
        token.pending = len(live)
        # Sampled spans riding this run (usually empty). The run span links
        # them to the pipeline window they shared.
        spans = [op.span for op in live if op.span is not None]
        if spans and self._trace is not None:
            run_span = token.run_span = self._trace.begin_run(
                kind, target, len(live), token.nkeys)
            for s in spans:
                s.run_id = run_span.span_id
                s.event("dispatched", t0)
        parked = kind in PARKED_KINDS
        if not parked:
            # Attach completion accounting BEFORE the backend sees the ops: a
            # synchronous backend resolves futures inside run(), and the last
            # resolution must find the counter armed. Parked kinds skip this
            # entirely — their completion is driven by a later op, so their
            # "latency" is wait time, which must poison neither the window
            # nor the cost model's service EWMA.
            for op in live:
                op.future.add_done_callback(
                    lambda fut, token=token, op=op: self._op_done(
                        token, fut, op))
        journal = self._journal
        if journal is not None and not parked:
            # Write-ahead ordering: the record reaches the journal before
            # the backend commits state at stage time, so an acknowledged
            # op is always journaled (read kinds are a no-op inside
            # append_run). `defer` hints that more dispatch work is queued,
            # letting the "always" policy group-commit one fsync across
            # the pipeline window instead of paying one per run.
            try:
                # graftlint: allow-guarded(advisory group-commit hint: a stale _ready read only costs one extra fsync)
                journal.append_run(kind, live, defer=bool(self._ready))
                if spans:
                    t_j = self._clock()
                    for s in spans:
                        s.event("journaled", t_j)
                    if token.run_span is not None:
                        token.run_span.event("journaled", t_j)
            except Exception as exc:
                # A journal that cannot accept the record must fail the
                # ops — applying an unjournaled mutation would silently
                # break the recovery contract. Nothing has committed yet,
                # so classification lands on the retryable side and the
                # serve layer re-dispatches after backoff.
                exc = classify(exc, seam="journal_fsync")
                token.failed = True
                if m:
                    m.record_error(kind)
                for s in spans:
                    # Annotate BEFORE resolving futures: the done-callback
                    # finishes the span, and the slowlog entry must carry
                    # the injected seam.
                    s.annotate(fault=type(exc).__name__,
                               seam=getattr(exc, "seam", "journal_fsync"))
                for op in live:
                    if not op.future.done():
                        op.future.set_exception(exc)
                return
        try:
            fault_inject.fire("kernel_launch", kind=kind, target=target)
            with stage("backend.run", ops=len(live)):
                self._backend.run(kind, target, live)
            t_staged = self._clock()
            token.stage_s = t_staged - t0
            if spans:
                # A synchronous backend resolves futures inside run(), so a
                # span may already be finished here — don't stamp those (its
                # device stage then absorbs run(), which is the truth for an
                # inline backend).
                for s in spans:
                    if s.t1 is None:
                        s.event("staged", t_staged)
                if token.run_span is not None:
                    token.run_span.event("staged", t_staged)
            od = getattr(self._policy, "observe_dispatch", None)
            if od is not None:
                # Staging-side cost signal (host prep only — NOT service
                # time; the cost model's service EWMA feeds from completion).
                od(kind, token.nkeys, token.stage_s)
            if self._eager_release and not parked:
                # Dispatch-time-state backend: all observable state is
                # committed once run() returns, so the next run for these
                # targets may stage immediately; only the in-flight window
                # still bounds depth.
                self._release_gates(token)
        except Exception as exc:  # complete, never kill the loop
            # The staging boundary: H2D copies, jit dispatch, and the
            # injected kernel_launch seam all surface here. classify()
            # decides whether the serve layer may re-dispatch (RetryableFault
            # — nothing committed) or the rebuild path must re-materialize
            # (StateUncertainFault, noted by _op_done below).
            exc = classify(exc, seam="kernel_launch")
            token.failed = True
            token.stage_s = self._clock() - t0
            if m:
                m.record_error(kind)
            for s in spans:
                if s.t1 is None:
                    s.annotate(fault=type(exc).__name__,
                               seam=getattr(exc, "seam", "kernel_launch"))
            for op in live:
                if not op.future.done():
                    op.future.set_exception(exc)
        self._stage_ns += int(token.stage_s * 1e9)
        self._runs_staged += 1
        if parked:
            # The waiter is parked (or was served/failed inline); drop the
            # gates and the window slot now — the fulfilling op must be able
            # to dispatch against this same target.
            for s in spans:
                # Parked kinds attach no done-callback — their latency is
                # wait time. Close the span at park so it measures dispatch,
                # not how long the waiter chose to wait.
                if s.t1 is None:
                    s.annotate(parked=True)
                    s.finish()
            self._retire(token, completed=False)

    # -- completion path ----------------------------------------------------

    def _op_done(self, token: _InflightRun, fut=None, op: Optional[Op] = None) -> None:
        """Done-callback on each live op future; runs on whichever thread
        resolves it (the backend completer, or the dispatcher itself for
        synchronous backends)."""
        if op is not None and op.span is not None and op.span.t1 is None:
            span = op.span
            span.event("completed")
            err = None
            if fut is not None and not fut.cancelled():
                exc = fut.exception()
                if exc is not None:
                    err = type(exc).__name__
                    seam = getattr(exc, "seam", None)
                    if seam is not None:
                        span.annotations.setdefault("seam", seam)
            span.finish(error=err)
        exc = None
        if fut is not None and not fut.cancelled():
            exc = fut.exception()
        with token.lock:
            if exc is not None:
                # A backend that isolates failures per op/group (the delta
                # window) completes futures with exceptions instead of
                # raising out of run() — the error metric must still see
                # the run. Written under token.lock: callbacks for one run
                # race each other across completer threads, and the
                # release that drops `pending` to 0 is what publishes
                # these to _run_completed's thread.
                token.op_failed = True
                if token.fault_exc is None and \
                        isinstance(exc, StateUncertainFault):
                    # State-uncertain retirement (device loss, watchdog
                    # trip, post-dispatch transfer death): remember the
                    # first such fault so _run_completed can hand the
                    # run's targets to the rebuild listener.
                    token.fault_exc = exc
            token.pending -= 1
            if token.pending > 0:
                return
        self._run_completed(token)

    def _run_completed(self, token: _InflightRun) -> None:
        """The whole run's results have landed: this is where service time
        becomes observable (device compute + D2H, not just host staging), so
        the cost model and latency metrics feed from HERE — the dispatcher's
        own wall-clock around run() collapses to staging time once dispatch
        stops blocking on results."""
        dt = self._clock() - token.t0
        if token.op_failed and not token.failed and self._metrics:
            # failed (staging raised) already recorded the error inline;
            # count per-op failures once per run, like a staging failure.
            self._metrics.record_error(token.kind)
        if not token.failed:
            self._policy.observe(token.kind, token.nkeys, dt)
            if self._metrics:
                self._metrics.record_batch(
                    token.kind, token.nops, token.nkeys, dt,
                    queue_delay_s=token.queue_delay_s,
                    cap=self._max_batch_keys,
                    stage_s=token.stage_s)
        self._retire(token, completed=True)
        listener = self.fault_listener
        if listener is not None and token.fault_exc is not None:
            try:
                listener(token.kind, token.targets, token.fault_exc)
            except Exception:
                # graftlint: allow-bare(the rebuild listener is best-effort; a listener bug must not poison the completion path that just resolved the futures)
                pass

    def _release_gates_locked(self, token: _InflightRun) -> None:
        if not token.gates_held:
            return
        token.gates_held = False
        self._inflight_targets.difference_update(token.targets)
        if token.is_global:
            self._inflight_kinds.discard(self._group_of(token.kind))

    def _release_gates(self, token: _InflightRun) -> None:
        with self._cv:
            self._release_gates_locked(token)
            self._cv.notify_all()

    def _retire(self, token: _InflightRun, completed: bool) -> None:
        run_span = token.run_span
        if run_span is not None:
            token.run_span = None
            run_span.event("completed")
            run_span.finish(
                error=type(token.fault_exc).__name__
                if token.fault_exc is not None else None)
        with self._cv:
            self._release_gates_locked(token)
            self._inflight.discard(token)
            self._staging_bytes -= token.staged_bytes
            token.staged_bytes = 0
            if completed:
                self._runs_completed += 1
                if token.overlapped:
                    self._runs_overlapped += 1
            self._cv.notify_all()
        if completed and self._metrics:
            self._metrics.record_run(token.depth, token.overlapped)

    def pipeline_stats(self) -> Dict[str, Any]:
        """Live pipeline counters (suite --pipeline-smoke + serve snapshot):
        overlap_ratio is the fraction of completed runs that were dispatched
        while at least one other run was still in flight; stage_ns sums
        the host staging time of runs_staged runs; with metrics attached,
        `{read,write}_wait_le_us.<bound>` count the ops whose enqueue to
        dispatch wait fell in the histogram bucket ending at <bound> us."""
        with self._lock:
            done = self._runs_completed
            out = {
                "window": self._window,
                "eager_release": self._eager_release,
                "inflight": len(self._inflight),
                "runs_completed": done,
                "runs_overlapped": self._runs_overlapped,
                "overlap_ratio": (self._runs_overlapped / done) if done else 0.0,
                "staging_bytes": self._staging_bytes,
                "shard_tag": self.shard_tag,
                "stage_ns": self._stage_ns,
                "runs_staged": self._runs_staged,
            }
        if self._metrics:
            out.update(self._metrics.queue_wait_buckets())
        return out

    def staging_bytes(self) -> int:
        """In-flight payload bytes (memstat 'staging' meter)."""
        with self._lock:
            return self._staging_bytes

    # -- fault-subsystem surface -------------------------------------------

    def fail_inflight(self, token: _InflightRun, exc: BaseException) -> int:
        """Resolve a stuck run's still-pending futures with `exc` (the
        watchdog's trip action). Completion flows through the normal
        done-callback path, so the run retires and its gates release; a
        late device completion finds the futures done and is dropped by
        the backend's `future.done()` guards. Returns how many futures
        this call resolved."""
        failed = 0
        for op in token.ops:
            if op.future.done():
                continue
            try:
                op.future.set_exception(exc)
                failed += 1
            except Exception:
                # graftlint: allow-bare(InvalidStateError race: the completer resolved this future between the done() check and here — exactly the outcome we wanted)
                pass
        return failed

    def sweep_queued(self, targets, exc_factory) -> int:
        """Complete every QUEUED (undispatched) op for `targets` with
        `exc_factory(op)` — the rebuild path cancels dependents of a
        quarantined target this way (they were never dispatched, so a
        retryable rejection is safe and the serve layer re-lands them
        after the rebuild). Returns the number of swept ops."""
        targets = set(targets)
        with self._cv:
            swept: List[Op] = []
            for t in targets:
                q = self._queues.get(t)
                if not q:
                    continue
                swept.extend(q)
                q.clear()
                del self._queues[t]
                if t in self._ready:
                    self._ready.remove(t)
        for op in swept:
            if not op.future.done():
                op.future.set_exception(exc_factory(op))
            if op.span is not None and op.span.t1 is None:
                op.span.annotate(swept=True)
                op.span.finish(error="swept")
        return len(swept)

    def _cancel_remaining(self) -> None:
        """Drain every queue and cancel the stranded ops' futures, so
        `result()` raises CancelledError instead of hanging forever after
        the dispatcher is gone (shutdown satellite fix)."""
        with self._cv:
            pending = [op for q in self._queues.values() for op in q]
            self._queues.clear()
            self._ready.clear()
        cancelled = 0
        for op in pending:
            if op.future.cancel():
                op.future.set_running_or_notify_cancel()
                cancelled += 1
            if op.span is not None and op.span.t1 is None:
                op.span.finish(error="CancelledError")
        if cancelled and self._metrics:
            self._metrics.record_cancelled(cancelled)

    def is_alive(self) -> bool:
        """Liveness probe for the replica tier's failover health check:
        True while the dispatcher thread runs and shutdown hasn't begun.
        (A dispatcher that died to an unhandled error — or a primary whose
        process-level kill was simulated by shutdown — reads False and
        trips the ReplicaManager's consecutive-failure counter.)"""
        # graftlint: allow-guarded(liveness probe: a racy _shutdown read flips one probe round late, the failure counter absorbs it)
        return not self._shutdown and self._thread.is_alive()

    def shutdown(self, wait: bool = True, timeout: float = 30.0):
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()
        if wait:
            t_end = time.monotonic() + timeout
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                # Dispatcher wedged inside backend.run past the join budget:
                # the in-flight run belongs to the backend, but everything
                # still queued behind it would hang its waiters forever —
                # cancel those now. (A clean drain leaves the queues empty
                # and this is a no-op.)
                self._cancel_remaining()
                return
            # Queues drained; now drain the in-flight window too (bounded by
            # the same budget) so a clean shutdown implies every dispatched
            # run's futures resolved — the backend completer is still alive
            # at this point, client teardown stops it after us.
            with self._cv:
                while self._inflight:
                    remaining = t_end - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cv.wait(remaining)

    # -- batch facade -------------------------------------------------------

    def batch(self, **submit_kwargs) -> "BatchCollector":
        return BatchCollector(self, **submit_kwargs)


class BatchCollector:
    """RBatch engine: collect ops without dispatching, then execute.

    Reference: `command/CommandBatchService.java` — collect phase appends
    indexed commands per slot; execute sends pipelines and reassembles
    results by global index (`:163-174`). Here the executor's queues are the
    pipelines; we hold ops back until execute() so the collect phase does no
    I/O, then submit in index order and gather results in the same order.

    `submit_kwargs` (tenant / deadline / timeout, serving-layer mode) apply
    to the WHOLE batch at dispatch time: one admission decision, one budget.
    """

    def __init__(self, executor, **submit_kwargs):
        self._executor = executor
        self._submit_kwargs = submit_kwargs
        self._staged: List[tuple] = []
        self._futures: List["StagedFuture"] = []
        self._executed = False

    def add(self, target: str, kind: str, payload: Any, nkeys: int = 0) -> "StagedFuture":
        """Stage an op; returns its placeholder future (resolved at execute)."""
        if self._executed:
            raise RuntimeError("batch already executed")
        self._staged.append((target, kind, payload, nkeys))
        f = StagedFuture()
        self._futures.append(f)
        return f

    def _dispatch(self) -> List[Future]:
        if self._executed:
            raise RuntimeError("batch already executed")
        self._executed = True
        for f in self._futures:
            f._dispatched = True
        # One submission for the whole pipeline: the executor (or serving
        # layer) admits and deadline-stamps the batch as a unit.
        inner = self._executor.execute_many(self._staged, **self._submit_kwargs)
        for staged, src in zip(self._futures, inner):
            src.add_done_callback(staged._resolve_from)
        return inner

    def execute(self) -> List[Any]:
        """Dispatch all staged ops; decoded results in global-index order.

        Per-op decode chains registered via `map_future` fire off the staged
        futures, so the returned list carries the same values the async
        getters' futures resolve to (reference: converted batch replies,
        `CommandBatchService.java:163-174`)."""
        inner = self._dispatch()
        for f in inner:
            # Propagate the first failure like the reference's batch promise.
            # graftlint: allow-g006(RBatch.execute is the blocking facade — the dispatcher resolves these in submission order, and serve-mode batches carry a deadline that bounds the wait)
            f.result()
        # graftlint: allow-g006(same blocking-facade contract as the loop above; inner futures are already resolved here)
        return [f.outermost().result() for f in self._futures]

    def execute_async(self) -> List[Future]:
        """Dispatch staged ops; returns the decoded per-op futures in order."""
        self._dispatch()
        return [f.outermost() for f in self._futures]


class StagedFuture(Future):
    """RBatch placeholder: a real Future resolved only at execute() time.

    Calling result() before the batch is dispatched raises (the reference's
    batch commands cannot be awaited before `RBatch.execute()` either)
    instead of deadlocking; after dispatch it blocks normally until the
    dispatcher thread resolves it. Waiting on an un-dispatched StagedFuture
    through a raw waiter (asyncio.wrap_future, futures.wait) will block
    until execute() is called — use result()/the batch return value instead.
    Decode wrappers chained by `map_future` register themselves via
    `_note_mapped` so the batch can return decoded values.
    """

    def __init__(self):
        super().__init__()
        self._dispatched = False
        self._mapped: Future = self

    def result(self, timeout=None):
        if not self._dispatched and not self.done():
            raise RuntimeError("batch not executed yet; call RBatch.execute()")
        return super().result(timeout)

    def _resolve_from(self, src: Future) -> None:
        if src.cancelled():
            self.cancel()
            self.set_running_or_notify_cancel()
            return
        exc = src.exception()
        if exc is not None:
            self.set_exception(exc)
        else:
            # graftlint: allow-g006(done-callback context: src is already resolved, result() cannot block)
            self.set_result(src.result())

    def _note_mapped(self, fut: Future) -> None:
        self._mapped = fut

    def outermost(self) -> Future:
        """The outermost decode wrapper (or self if none was chained)."""
        return self._mapped
