"""Observability: metrics registry, executor instrumentation, and the
nodes/health API.

The reference has NO metrics or tracing (SURVEY.md §5 — slf4j logs only);
its only health surface is `NodesGroup.pingAll`/`Node.ping`
(RedisNodes.java, core/Node.java) and the connect/disconnect callbacks of
`ConnectionEventsHub`. For a framework that owns device state, first-class
metrics and an XLA profiler hook are required new design, not a port:

  * MetricsRegistry — thread-safe counters / gauges / histograms with a
    prometheus-text renderer and a dict snapshot;
  * executor instrumentation — per-kind op counts, coalesced batch-size and
    dispatch-latency histograms, live queue depth (wired by the executor
    when a registry is attached), and every op's enqueue -> dispatch wait
    split into reads and writes;
  * NodesGroup — ping of every compute node (device micro-kernel
    round-trip) and the redis durability tier (RESP PING), plus
    connect/disconnect listener fan-out (the ConnectionEventsHub role).
"""

from __future__ import annotations

import bisect
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from redisson_tpu.commands import OP_TABLE
from redisson_tpu.trace.hist import LatencyHistogram

#: op kinds the command table marks as mutating (queue waits split on it)
_WRITE_KINDS = frozenset(k for k, d in OP_TABLE.items() if d.write)

# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

_DEFAULT_BUCKETS = (
    1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0, float("inf"))


class Histogram:
    __slots__ = ("buckets", "counts", "total", "sum", "min", "max", "_lock")

    def __init__(self, buckets=_DEFAULT_BUCKETS):
        self.buckets = tuple(buckets)
        self.counts = [0] * len(self.buckets)
        self.total = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        with self._lock:
            i = bisect.bisect_left(self.buckets, value)
            self.counts[min(i, len(self.counts) - 1)] += 1
            self.total += 1
            self.sum += value
            self.min = min(self.min, value)
            self.max = max(self.max, value)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "count": self.total,
                "sum": self.sum,
                "min": self.min if self.total else None,
                "max": self.max if self.total else None,
                "mean": (self.sum / self.total) if self.total else None,
                "buckets": dict(zip(map(str, self.buckets), self.counts)),
            }


class MetricsRegistry:
    """Names are dotted strings; labels are a frozen kwargs suffix."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, Callable[[], float]] = {}
        self._histograms: Dict[str, Histogram] = {}

    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def gauge(self, name: str, fn: Callable[[], float]) -> None:
        with self._lock:
            self._gauges[name] = fn

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram()
            return h

    def observe(self, name: str, value: float) -> None:
        self.histogram(name).observe(value)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            gauges = {k: fn for k, fn in self._gauges.items()}
            hists = dict(self._histograms)
        out: Dict[str, Any] = {"gauges": {}, "histograms": {}}
        for k, fn in gauges.items():
            # A raising gauge callback is dropped from this snapshot and
            # counted, never poisons the rest (one broken subsystem must
            # not take down the whole observability surface).
            try:
                out["gauges"][k] = fn()
            except Exception:
                self.inc("metrics.callback_errors")
        for k, h in hists.items():
            out["histograms"][k] = h.snapshot()
        # Counters copied after gauge evaluation so callback_errors bumps
        # from THIS snapshot are already visible in it.
        with self._lock:
            out["counters"] = dict(self._counters)
        return out

    def render_prometheus(self) -> str:
        """Text exposition format: counters, gauges, and *scrapeable*
        histogram families — cumulative `_bucket{le=...}` series in
        ascending bound order ending with the mandatory `le="+Inf"`
        (Prometheus spells infinity that way, not `inf`), plus `_sum`
        and `_count`. `_count` always equals the `+Inf` bucket."""
        snap = self.snapshot()
        lines: List[str] = []

        def sanitize(name: str) -> str:
            return name.replace(".", "_").replace("-", "_")

        def fmt_bound(v: float) -> str:
            if v == float("inf"):
                return "+Inf"
            s = repr(float(v))
            return s[:-2] if s.endswith(".0") else s

        for k, v in sorted(snap["counters"].items()):
            lines.append(f"# TYPE {sanitize(k)} counter")
            lines.append(f"{sanitize(k)} {v}")
        for k, v in sorted(snap["gauges"].items()):
            lines.append(f"# TYPE {sanitize(k)} gauge")
            lines.append(f"{sanitize(k)} {v if v is not None else 'NaN'}")
        for k, h in sorted(snap["histograms"].items()):
            base = sanitize(k)
            lines.append(f"# TYPE {base} histogram")
            cumulative = 0
            saw_inf = False
            for le, c in h["buckets"].items():
                bound = float(le)
                cumulative += c
                saw_inf = saw_inf or bound == float("inf")
                lines.append(
                    f'{base}_bucket{{le="{fmt_bound(bound)}"}} {cumulative}')
            if not saw_inf:
                # Custom bucket ladders without an explicit inf bound still
                # need the +Inf series (scrapers reject histograms without
                # it); overflow observations were clamped into the last
                # bucket, so the running cumulative == count here.
                lines.append(f'{base}_bucket{{le="+Inf"}} {h["count"]}')
            lines.append(f"{base}_sum {h['sum']}")
            lines.append(f"{base}_count {h['count']}")
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Nodes / health (NodesGroup + ConnectionEventsHub analogue)
# ---------------------------------------------------------------------------


@dataclass
class Node:
    """One compute or durability node."""

    kind: str  # "device" | "redis"
    ident: str
    _pinger: Callable[[], bool] = field(repr=False, default=None)

    def ping(self) -> bool:
        try:
            return bool(self._pinger())
        except Exception:
            return False

    def get_type(self) -> str:
        """Reference Node.getType() (NodeType analogue: the tier kind)."""
        return self.kind

    def get_addr(self) -> str:
        """Reference Node.getAddr()."""
        return self.ident

    def info(self) -> dict:
        """Reference ClusterNode.info(): the node's descriptive fields."""
        return {"type": self.kind, "addr": self.ident,
                "alive": self.ping()}


class NodesGroup:
    """client.get_nodes_group(): enumerate + ping nodes, listen to
    connect/disconnect events from the durability tier."""

    def __init__(self, client):
        self._client = client
        self._listeners: List[Callable[[str, str], None]] = []

    def nodes(self) -> List[Node]:
        import jax

        out: List[Node] = []
        for d in jax.devices():
            out.append(Node("device", str(d), _device_pinger(d)))
        if getattr(self._client, "_resp", None) is not None:
            resp = self._client._resp

            def ping_redis() -> bool:
                return resp.execute("PING") in (b"PONG", b"pong")

            out.append(Node("redis", f"{resp.host}:{resp.port}", ping_redis))
        return out

    def ping_all(self) -> bool:
        return all(n.ping() for n in self.nodes())

    def add_connection_listener(self, fn: Callable[[str, str], None]) -> None:
        """fn(event, ident) with event in {'connect', 'disconnect'}."""
        self._listeners.append(fn)

    def remove_connection_listener(self, fn: Callable[[str, str], None]) -> None:
        try:
            self._listeners.remove(fn)
        except ValueError:
            pass

    def fire(self, event: str, ident: str) -> None:
        for fn in list(self._listeners):
            try:
                fn(event, ident)
            except Exception:
                pass


def _device_pinger(device) -> Callable[[], bool]:
    def ping() -> bool:
        import jax.numpy as jnp

        x = jnp.zeros((8,), jnp.int32)
        import jax

        y = jax.device_put(x, device) + 1
        return int(y.sum()) == 8

    return ping


# ---------------------------------------------------------------------------
# Executor instrumentation helper
# ---------------------------------------------------------------------------


class ExecutorMetrics:
    """Attached to a CommandExecutor: the dispatcher reports op/batch/latency
    stats here. Cheap enough for the hot path (a few dict ops per BATCH,
    not per key)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry or MetricsRegistry()
        # Every dispatched op's enqueue -> dispatch wait, unsampled, at the
        # histogram's 3 % resolution (written by the dispatcher only).
        self.queue_wait = {"read": LatencyHistogram(),
                           "write": LatencyHistogram()}

    def record_queue_waits(self, ops: Sequence[Any], t_dispatch: float) -> None:
        """One run dispatched at `t_dispatch` (executor clock): each op's
        wait since its enqueue, by the op table's write flag."""
        read, write = self.queue_wait["read"], self.queue_wait["write"]
        for op in ops:
            h = write if op.kind in _WRITE_KINDS else read
            h.record(t_dispatch - op.enqueued_at)

    def queue_wait_buckets(self) -> Dict[str, int]:
        """The wait histograms flattened for counter snapshots:
        `<read|write>_wait_le_us.<upper bound in us>` -> ops, for every
        non-empty bucket (counts only grow, so deltas of two snapshots are
        the waits between them)."""
        return {f"{side}_wait_le_us.{upper}": count
                for side, h in self.queue_wait.items()
                for upper, count in h.buckets()}

    def record_batch(self, kind: str, nops: int, nkeys: int,
                     latency_s: float, queue_delay_s: Optional[float] = None,
                     cap: int = 0, stage_s: Optional[float] = None) -> None:
        r = self.registry
        r.inc(f"executor.ops.{kind}", nops)
        r.inc("executor.ops_total", nops)
        r.inc("executor.keys_total", nkeys)
        r.inc("executor.batches_total")
        r.observe("executor.batch_ops", nops)
        r.observe("executor.batch_keys", nkeys)
        # With pipelined dispatch this is completion latency (stage + device
        # compute + D2H), observed when the run's last future resolves.
        r.observe(f"executor.latency_s.{kind}", latency_s)
        if stage_s is not None:
            # Host-side staging cost alone (pad + device_put + enqueue) —
            # the dispatcher-thread share of the latency above.
            r.observe(f"executor.stage_s.{kind}", stage_s)
        if queue_delay_s is not None:
            # Oldest-op wait from enqueue to dispatch: THE serving-latency
            # number admission control exists to bound.
            r.observe("executor.queue_delay_s", max(0.0, queue_delay_s))
        if cap > 0:
            r.observe("executor.batch_occupancy", nkeys / cap)

    def record_run(self, inflight_depth: int, overlapped: bool) -> None:
        """One pipelined run retired: depth seen at its dispatch, and
        whether another run was already in flight then (overlap)."""
        r = self.registry
        r.inc("executor.runs_total")
        r.observe("executor.inflight_depth", inflight_depth)
        if overlapped:
            r.inc("executor.runs_overlapped_total")

    def record_error(self, kind: str) -> None:
        self.registry.inc(f"executor.errors.{kind}")
        self.registry.inc("executor.errors_total")

    def record_expired(self, kind: str, nops: int) -> None:
        """Ops whose deadline passed before device dispatch."""
        self.registry.inc(f"executor.expired.{kind}", nops)
        self.registry.inc("executor.expired_total", nops)

    def record_cancelled(self, nops: int) -> None:
        """Ops still queued when the dispatcher exited (shutdown sweep)."""
        self.registry.inc("executor.cancelled_total", nops)


def register_read_cache(registry: MetricsRegistry, cache) -> None:
    """Expose a backend's epoch-stamped read cache (hits / misses / hit
    ratio / live entries) as gauges — the client wires this when the sketch
    backend carries one (client-side-caching observability analogue)."""
    registry.gauge("backend.read_cache_hits", lambda: cache.hits)
    registry.gauge("backend.read_cache_misses", lambda: cache.misses)
    registry.gauge("backend.read_cache_entries", lambda: len(cache))

    def _ratio() -> float:
        total = cache.hits + cache.misses
        return (cache.hits / total) if total else 0.0

    registry.gauge("backend.read_cache_hit_ratio", _ratio)


def register_delta_ingest(registry: MetricsRegistry, backend) -> None:
    """Expose a backend's delta-ingest counters (see TpuBackend.counters /
    ingest_stats) as backend.* gauges: link bytes actually shipped vs what
    the raw-key path would have cost, host fold time, and fused merge
    launches — the observable core of the delta tentpole (link compression
    and one-launch-per-window retirement)."""
    def _stat(key, default=0):
        return lambda: backend.ingest_stats().get(key, default)

    registry.gauge("backend.link_bytes", _stat("link_bytes"))
    registry.gauge("backend.raw_bytes", _stat("raw_bytes"))
    registry.gauge("backend.delta_fold_s", _stat("delta_fold_s", 0.0))
    registry.gauge("backend.merge_launches", _stat("merge_launches"))
    registry.gauge("backend.delta_runs", _stat("delta_runs"))
    registry.gauge("backend.delta_keys", _stat("delta_keys"))
    registry.gauge("backend.delta_bytes_per_key",
                   _stat("delta_bytes_per_key", 0.0))


def register_persist(registry: MetricsRegistry, manager) -> None:
    """Expose the durability subsystem (persist/) as persist.* gauges:
    journal throughput and group-commit behavior, snapshot cadence, and —
    when the manager recovered at startup — the replay rate. Follower lag
    lives on the follower's own client registry (register_follower)."""
    def _journal(key, default=0):
        def read():
            j = manager.journal
            return j.stats().get(key, default) if j is not None else default
        return read

    registry.gauge("persist.appended", _journal("records_appended"))
    registry.gauge("persist.runs_appended", _journal("runs_appended"))
    registry.gauge("persist.bytes_appended", _journal("bytes_appended"))
    registry.gauge("persist.fsyncs", _journal("fsyncs"))
    registry.gauge("persist.group_mean", _journal("group_mean", 0.0))
    registry.gauge("persist.last_seq", _journal("last_seq"))
    registry.gauge("persist.durable_seq", _journal("durable_seq"))
    registry.gauge("persist.unsynced_runs", _journal("unsynced_runs"))
    registry.gauge("persist.segments", _journal("segments"))
    registry.gauge(
        "persist.snapshots_taken",
        lambda: manager.snapshotter.snapshots_taken if manager.snapshotter else 0)
    registry.gauge(
        "persist.snapshot_seq",
        lambda: manager.snapshotter.last_seq if manager.snapshotter else 0)
    registry.gauge(
        "persist.replay_ops_s",
        lambda: (manager.last_recovery or {}).get("ops_per_s", 0.0))
    registry.gauge(
        "persist.replayed",
        lambda: (manager.last_recovery or {}).get("replayed", 0))


def register_fault(registry: MetricsRegistry, manager) -> None:
    """Expose the fault subsystem (fault/) as fault.* gauges: injection
    volume, classification outcomes, retry pressure attributable to
    device faults, and the rebuild loop's progress. `manager` is a
    fault.manager.FaultManager; its injector/watchdog/rebuild members may
    each be None (gauges then read 0)."""
    from redisson_tpu.fault import taxonomy

    registry.gauge(
        "fault.injected",
        lambda: manager.injector.injected if manager.injector else 0)
    registry.gauge(
        "fault.classified", lambda: taxonomy.stats()["classified"])
    # Serve-layer retries fire on RetryableError, whose device-fault
    # subclass is RetryableFault — the retry counter is the observable
    # "faults the retry machinery absorbed" signal.
    registry.gauge(
        "fault.retried", lambda: registry.counter("serve.retries_total"))
    registry.gauge(
        "fault.rebuilt",
        lambda: manager.rebuild.rebuilt_total if manager.rebuild else 0)
    registry.gauge(
        "fault.quarantined",
        lambda: (manager.rebuild.quarantined_total
                 if manager.rebuild else 0))
    registry.gauge(
        "fault.degraded",
        lambda: (len(manager.rebuild.snapshot()["degraded"])
                 if manager.rebuild else 0))
    registry.gauge(
        "fault.rebuild_s",
        lambda: manager.rebuild.last_rebuild_s if manager.rebuild else 0.0)
    registry.gauge(
        "fault.watchdog_trips",
        lambda: manager.watchdog.trips if manager.watchdog else 0)


def register_trace(registry: MetricsRegistry, manager) -> None:
    """Expose the trace subsystem (trace/) as trace.* gauges: sampling
    volume, span throughput, slowlog pressure and monitor fan-out health.
    `manager` is a trace.manager.TraceManager."""
    tracer = manager.tracer
    registry.gauge("trace.sampled", lambda: tracer.sampled)
    registry.gauge("trace.skipped", lambda: tracer.skipped)
    registry.gauge("trace.spans_finished", lambda: tracer.finished)
    registry.gauge("trace.slowlog_len", lambda: len(manager.slowlog))
    registry.gauge("trace.slowlog_total",
                   lambda: manager.slowlog.total_logged)
    registry.gauge("trace.monitor_subscribers",
                   lambda: manager.monitor.active())
    registry.gauge("trace.monitor_dropped", lambda: manager.monitor.dropped())
    registry.gauge("trace.retries", lambda: manager.retries)


def register_wire(registry: MetricsRegistry, wire) -> None:
    """Expose the RESP wire front-end (wire/) as wire.* gauges: connection
    population, in-flight pipeline pressure, byte throughput, shed volume
    and the connection-scheduler's window coalescing depth. `wire` is a
    wire.server.WireServer or (cluster facade) ClusterWireFrontend — both
    expose the same counters; the frontend sums across shard servers."""
    def _snap(key, default=0):
        return lambda: wire.snapshot().get(key, default)

    registry.gauge("wire.connections", wire.connections)
    registry.gauge("wire.connections_total", _snap("total_connections"))
    registry.gauge("wire.inflight", wire.inflight)
    registry.gauge("wire.bytes_in", _snap("bytes_in"))
    registry.gauge("wire.bytes_out", _snap("bytes_out"))
    registry.gauge("wire.commands", _snap("commands_total"))
    registry.gauge("wire.engine_commands", _snap("engine_commands"))
    registry.gauge("wire.sheds", _snap("sheds_total"))
    registry.gauge("wire.redirects", _snap("redirects_rendered"))
    registry.gauge("wire.windows", _snap("windows_flushed"))
    registry.gauge("wire.pipeline_depth", _snap("latest_window_depth"))
    registry.gauge("wire.pipeline_depth_avg", _snap("avg_window_depth", 0.0))
    registry.gauge("wire.dropped_conns", _snap("dropped_conns"))
    # loop-stall witness feed: zeros unless REDISSON_TPU_LOOP_WITNESS=1
    # armed the witness for this server's loop
    registry.gauge("wire.loop_lag_p99_us", _snap("loop_lag_p99_us"))
    registry.gauge("wire.loop_stalls", _snap("loop_stalls"))


def register_memstat(registry: MetricsRegistry, ledger,
                     pressure=None) -> None:
    """Expose the memstat ledger as memstat.* gauges: exact live/peak
    device bytes, per-kind totals, sampled meter categories, and (when a
    watermark is configured) the pressure gate's shed count. `ledger` is
    a memstat.MemLedger; scrapes ride render_prometheus like every other
    subsystem."""
    registry.gauge("memstat.live_bytes", ledger.live_bytes)
    registry.gauge("memstat.peak_bytes", ledger.peak_bytes)
    registry.gauge("memstat.keys", ledger.keys_count)
    registry.gauge("memstat.bank_bytes", ledger.bank_bytes)
    registry.gauge("memstat.meter_errors", lambda: ledger.meter_errors)
    for kind in ("hll", "bitset", "bloom"):
        registry.gauge(f"memstat.{kind}_bytes",
                       lambda k=kind: ledger.kind_bytes().get(k, 0))
    for cat in ("cache", "scratch", "staging", "disk"):
        registry.gauge(f"memstat.{cat}_bytes",
                       lambda c=cat: ledger.meter_totals()[c])
    if pressure is not None:
        registry.gauge("memstat.shed_total", lambda: pressure.shed_total)
        registry.gauge("memstat.high_watermark_bytes",
                       lambda: pressure.config.high_watermark_bytes)


def register_follower(registry: MetricsRegistry, follower) -> None:
    """Bounded-lag gauge for a warm standby (persist/follower.py)."""
    registry.gauge("persist.follower_lag", follower.lag)
    registry.gauge("persist.follower_applied_seq", lambda: follower.applied_seq)


def register_replica(registry: MetricsRegistry, manager) -> None:
    """Read-replica fleet gauges (replica/manager.py): worst-case lag and
    lowest watermark across the fleet, PSYNC-parity resync counters
    (sync_full / sync_partial_ok), promotions, and the router's read
    routing split."""
    registry.gauge("replica.count", lambda: len(manager.replicas))
    registry.gauge("replica.max_lag", manager.max_lag)
    registry.gauge("replica.min_watermark", manager.min_watermark)
    registry.gauge("replica.full_resyncs", manager.full_resyncs)
    registry.gauge("replica.partial_resyncs", manager.partial_resyncs)
    registry.gauge("replica.promotions", lambda: manager.promotions)
    # Failover generation: which journal stream is live (0 = the original
    # primary's, N = the Nth promotee's epoch journal) and how many
    # demoted primaries the fleet still tracks for teardown.
    registry.gauge("replica.epoch", lambda: manager._epoch)
    registry.gauge("replica.retired_primaries",
                   lambda: len(manager._retired))
    registry.gauge("replica.reads",
                   lambda: manager.router.replica_reads if manager.router else 0)
    registry.gauge("replica.primary_fallbacks",
                   lambda: manager.router.primary_fallbacks if manager.router else 0)
    registry.gauge("replica.moved_retries",
                   lambda: (manager.router.replica_moved_retries
                            if manager.router else 0))


def register_geo(registry: MetricsRegistry, manager) -> None:
    """Geo-replication site gauges (geo/manager.py): cross-site link
    health (worst-case lag in records and seconds), LWW arbitration
    counters (applies / suppressions / DEL-race resurrections), and
    total bytes shipped vs what the raw key batches would have cost —
    the CRDT-plane compression the link exists for."""
    registry.gauge("geo.peers", lambda: len(manager.links))
    registry.gauge("geo.applied", lambda: manager.applier.applied)
    registry.gauge("geo.suppressed", lambda: manager.applier.suppressed)
    registry.gauge("geo.resurrections",
                   lambda: manager.applier.resurrections)

    def _worst(field):
        def read():
            lags = [l.lag()[field] for l in list(manager.links.values())]
            return max(lags) if lags else 0
        return read

    def _total(stat):
        def read():
            return sum(l.stats[stat] for l in list(manager.links.values()))
        return read

    registry.gauge("geo.max_lag_records", _worst("records"))
    registry.gauge("geo.max_lag_seconds", _worst("seconds"))
    registry.gauge("geo.link_bytes", _total("link_bytes"))
    registry.gauge("geo.raw_bytes", _total("raw_bytes"))
    registry.gauge("geo.partitions", _total("partitions"))
    registry.gauge("geo.repairs", _total("repairs"))
