"""L4 — the client facade.

Reference: `Redisson.java` (`create(Config)` picks a ConnectionManager,
`Redisson.java:96-120`; 60+ typed getters bind objects to the shared
CommandSyncService). Here create() picks a backend by config mode, wraps it
with the RoutingBackend (sketch tier + structure tier), builds the executor
waist around it, and the getters hand out objects bound to it.
"""

from __future__ import annotations

import threading as _threading
from typing import Callable, Optional

from redisson_tpu.codecs import get_codec
from redisson_tpu.config import Config, TpuConfig
from redisson_tpu.eviction import EvictionScheduler
from redisson_tpu.executor import CommandExecutor
from redisson_tpu.models.batch import RBatch
from redisson_tpu.models.bitset import RBitSet
from redisson_tpu.models.bloomfilter import RBloomFilter
from redisson_tpu.models.bucket import RAtomicDouble, RAtomicLong, RBucket, RBuckets
from redisson_tpu.models.collections import RList, RSet
from redisson_tpu.models.geo import RGeo
from redisson_tpu.models.hyperloglog import RHyperLogLog
from redisson_tpu.models.keys import RKeys
from redisson_tpu.models.lock import (
    LockWatchdog,
    RCountDownLatch,
    RFairLock,
    RLock,
    RMultiLock,
    RReadWriteLock,
    RSemaphore,
    new_client_id,
)
from redisson_tpu.models.map import RMap
from redisson_tpu.models.mapcache import RMapCache, RSetCache
from redisson_tpu.models.multimap import RListMultimap, RSetMultimap
from redisson_tpu.models.queue import RBlockingDeque, RBlockingQueue, RDeque, RQueue
from redisson_tpu.models.scoredsortedset import RLexSortedSet, RScoredSortedSet
from redisson_tpu.models.sortedset import RSortedSet
from redisson_tpu.models.topic import RPatternTopic, RTopic
from redisson_tpu.routing import RoutingBackend
from redisson_tpu.store import SketchStore


class RedissonTPU:
    """The RedissonClient analogue."""

    def __init__(self, config: Optional[Config] = None):
        self.config = config or Config()
        mode = self._mode = self.config.mode()
        self._codec = get_codec(self.config.codec)
        self.id = new_client_id()  # connection-manager UUID analogue
        # Cluster tier handle (cluster/): the ClusterManager on a cluster
        # facade client, None everywhere else (including shard members).
        self.cluster = None

        ccfg = self.config.cluster
        if ccfg is not None and ccfg.shard_id == -1:
            # Slot-sharded namespace: this client is the FACADE — it builds
            # N shard clients (each one re-enters __init__ with shard_id
            # >= 0) and dispatches through the ClusterRouter instead of its
            # own executor. The compute section (local/tpu) configures the
            # per-shard stacks, not this client.
            self._init_cluster_mode()
            self._start_wire()
            return
        if mode == "redis":
            # Passthrough: every op translates to Redis commands over RESP —
            # the reference's own execution model (server executes, client
            # is stateless).
            self._init_redis_mode()
            self._start_wire()
            return
        # Device-backed modes compile kernels: persist them across processes.
        from redisson_tpu.tpu_boot import enable_compilation_cache

        enable_compilation_cache()
        if mode == "pod":
            from redisson_tpu.parallel.backend_pod import PodBackend

            tcfg = self.config.pod
            if getattr(tcfg, "hll_hash", "murmur3") == "redis":
                raise NotImplementedError(
                    "hll_hash='redis' is a single-chip (local/tpu) mode "
                    "feature; the pod bank kernels and native pre-hash "
                    "implement the murmur3 family")
            sketch = PodBackend(tcfg)
            self._store = sketch.store
        else:
            # 'local' runs the same sketch engine on whatever platform jax
            # gives us (cpu in tests); 'tpu' expects a TPU device.
            import jax

            from redisson_tpu.backend_tpu import TpuBackend

            tcfg = self.config.tpu or TpuConfig()
            devices = jax.devices()
            if not 0 <= tcfg.device_index < len(devices):
                raise ValueError(
                    f"device_index {tcfg.device_index} is out of range: "
                    f"{len(devices)} device(s)")
            device = devices[tcfg.device_index]
            self._store = SketchStore(device=device)
            sketch = TpuBackend(
                self._store, hll_impl=tcfg.hll_impl, seed=tcfg.hash_seed,
                ingest=getattr(tcfg, "ingest", "auto"),
                hll_hash=getattr(tcfg, "hll_hash", "murmur3"),
                read_cache_entries=getattr(tcfg, "read_cache_entries", 1024),
            )
        self._routing = RoutingBackend(sketch)
        if ccfg is not None and ccfg.shard_id >= 0:
            # Shard member of a cluster: enforce slot ownership at the
            # dispatch waist. Installed HERE — before the executor and
            # before persist recovery — so replayed journal records cross
            # the same accept/reject boundary live traffic did.
            from redisson_tpu.cluster.shard import SlotOwnershipBackend

            self._routing = SlotOwnershipBackend(self._routing, ccfg.shard_id)
        elif ccfg is not None and ccfg.shard_id == -2:
            # Mesh data plane: this client is the ONE shared engine stack
            # behind every logical shard. Same waist, but the guard holds
            # the whole slot->shard table (MeshOwnershipBackend), and the
            # HLL bank goes onto a device mesh BEFORE any bank-touching
            # op — including persist recovery below — so every row the
            # engine ever materializes is mesh-sharded.
            from redisson_tpu.cluster.shard import MeshOwnershipBackend

            guard = MeshOwnershipBackend(self._routing, ccfg.num_shards)
            self._routing = guard
            if hasattr(sketch, "attach_mesh"):
                from redisson_tpu.parallel.mesh import SLOT_AXIS, get_mesh

                sketch.attach_mesh(get_mesh(axis=SLOT_AXIS),
                                   ccfg.num_shards, guard.shard_of_key)
        self._backend = self._routing
        self._widths = tuple(tcfg.key_width_buckets)
        from redisson_tpu.observability import MetricsRegistry

        self.metrics = MetricsRegistry()
        # memstat (memstat/): the exact byte ledger is ALWAYS on and must
        # be wired before any traffic can flow — persist recovery below
        # replays ops through the store seam, and those bytes must land
        # in the ledger like live traffic.
        from redisson_tpu.memstat import (MemLedger, MemoryReport,
                                          PressureMonitor)
        from redisson_tpu.observability import register_memstat

        self.memstat = MemLedger()
        self._store.accounting = self.memstat
        if hasattr(sketch, "_account_bank"):
            # Single-chip tier: bank lifecycle hooks + scratch meters.
            sketch.accounting = self.memstat
            sketch._account_bank()
            self.memstat.register_meter(
                "backend.bloom_mirrors",
                lambda s=sketch: s.scratch_bytes()["bloom_mirrors"],
                "scratch")
            self.memstat.register_meter(
                "backend.delta_scratch",
                lambda s=sketch: s.scratch_bytes()["delta_scratch"],
                "scratch")
        self._build_executor(self._routing, max_batch_keys=tcfg.max_batch_keys)
        if ccfg is not None and ccfg.shard_id >= 0:
            # Shard-tagged dispatch: pipeline_stats / traces carry which
            # shard's executor did the work (per-shard attribution).
            self._executor.shard_tag = ccfg.shard_id
        self.memstat.register_meter(
            "executor.staging", self._executor.staging_bytes, "staging")
        mcfg = self.config.memory
        self._pressure = None
        if mcfg is not None and mcfg.high_watermark_bytes > 0:
            self._pressure = PressureMonitor(self.memstat, mcfg)
        self._memreport = MemoryReport(
            self.memstat, store=self._store, backend=sketch,
            pressure=self._pressure)
        register_memstat(self.metrics, self.memstat, self._pressure)
        if self.serve is not None:
            self.serve.attach_memstat(self.memstat, self._pressure)
        if self.trace is not None:
            self.trace.attach_memstat(self.memstat)
        cache = getattr(sketch, "read_cache", None)
        if cache is not None:
            from redisson_tpu.observability import register_read_cache

            register_read_cache(self.metrics, cache)
            self.memstat.register_meter(
                "backend.read_cache", cache.content_bytes, "cache")
        if callable(getattr(sketch, "ingest_stats", None)):
            from redisson_tpu.observability import register_delta_ingest

            register_delta_ingest(self.metrics, sketch)
        self._pubsub = self._routing.pubsub
        self._watchdog = LockWatchdog(self._executor)
        self._eviction = EvictionScheduler(self._executor)

        # Durability tier: redis config alongside tpu/pod wires the flush
        # path (SURVEY.md §7 step 6); flush_interval_s > 0 starts the
        # periodic flusher.
        self._remote_services = {}
        self._durability = None
        self._resp = None
        # Native durability (persist/): journal + snapshots + auto-recover.
        # Wired BEFORE user traffic can flow (the getters don't exist yet)
        # and before the redis durability tier, so a recovered engine
        # flushes recovered state, not a partial one.
        self._persist = None
        pcfg = self.config.persist
        if pcfg is not None and pcfg.dir:
            from redisson_tpu.persist import PersistenceManager

            self._persist = PersistenceManager(self, pcfg)
            try:
                self._persist.start()
            except Exception:
                self.shutdown()
                raise
            if self.trace is not None:
                # Fsync durations feed LATENCY HISTORY + the fsync
                # histogram even for unsampled ops.
                journal = self._executor.journal
                if journal is not None:
                    journal.set_trace(self.trace)
            # On-disk byte meters (memstat 'disk' category): journal
            # segments + kept snapshot directories.
            journal = self._executor.journal
            if journal is not None:
                self.memstat.register_meter(
                    "persist.journal", journal.disk_bytes, "disk")
            if self._persist.snapshotter is not None:
                self.memstat.register_meter(
                    "persist.snapshots",
                    self._persist.snapshotter.disk_bytes, "disk")
        # Fault subsystem (fault/): taxonomy is always active (the backends
        # classify unconditionally); injection / watchdog / self-healing
        # rebuild only attach when Config.use_faults() was called. Wired
        # after persist so the rebuild path sees a recovered journal.
        self._fault = None
        fcfg = self.config.faults
        if fcfg is not None:
            from redisson_tpu.fault import FaultManager

            self._fault = FaultManager(self, fcfg)
            try:
                self._fault.start()
            except Exception:
                self.shutdown()
                raise
        # Read-replica fleet (replica/): N serving replicas tailing the
        # journal (persist IS the replication stream) + bounded-staleness
        # read routing + automatic failover. Wired after fault so the
        # DeviceLost trigger can chain onto the fault listener fan-out.
        self._replicas = None
        repcfg = self.config.replicas
        if repcfg is not None:
            if self._persist is None:
                self.shutdown()
                raise ValueError(
                    "Config.replicas requires Config.persist with a dir — "
                    "replicas tail that journal as the replication stream")
            from redisson_tpu.replica import ReplicaManager

            self._replicas = ReplicaManager(self, repcfg)
            try:
                self._replicas.start()
            except Exception:
                self.shutdown()
                raise
            # Model getters bind to _dispatch lazily, so every object
            # created from here on routes reads through the fleet.
            self._dispatch = self._replicas.router
            from redisson_tpu.observability import register_replica

            register_replica(self.metrics, self._replicas)
        # Geo-replication site (geo/): this engine becomes one active site
        # in a cross-site mesh; its journal ships to peers as CRDT delta
        # planes. Wired after replicas (both tail the same journal) —
        # peering happens at runtime via geo.connect_sites(...).
        self._geo = None
        gcfg = self.config.geo
        if gcfg is not None:
            if self._persist is None:
                self.shutdown()
                raise ValueError(
                    "Config.geo requires Config.persist with a dir — the "
                    "persist journal is the geo replication transport")
            from redisson_tpu.geo import GeoManager

            self._geo = GeoManager(self, gcfg)
            try:
                self._geo.start()
            except Exception:
                self.shutdown()
                raise
            from redisson_tpu.observability import register_geo

            register_geo(self.metrics, self._geo)
        if self.config.redis is not None and mode != "redis":
            try:
                self._connect_durability()
            except Exception:
                # Startup must not leak the already-running background
                # threads when the first dial fails.
                self.shutdown()
                raise
        # RESP wire front-end (wire/): the TCP listener real redis clients
        # connect to. Wired LAST so the first socket read finds the full
        # stack (serve admission, persist, replicas) already standing.
        self._start_wire()

    def _start_wire(self) -> None:
        """Start the wire front-end when `Config.wire` is set
        (PersistenceManager-style lifecycle: failures unwind the whole
        client). One WireServer in single-engine modes; the cluster facade
        starts one per shard behind a shared -MOVED/-ASK address table."""
        self.wire = None
        wcfg = self.config.wire
        if wcfg is None:
            return
        if self.cluster is not None:
            from redisson_tpu.wire import ClusterWireFrontend

            self.wire = ClusterWireFrontend(self, wcfg)
        else:
            from redisson_tpu.wire import WireServer

            self.wire = WireServer(self, wcfg)
        try:
            self.wire.start()
        except Exception:
            self.wire = None
            self.shutdown()
            raise
        if getattr(self, "metrics", None) is not None:
            from redisson_tpu.observability import register_wire

            register_wire(self.metrics, self.wire)

    def _build_executor(self, backend, max_batch_keys=None):
        """Build the executor waist and, when `Config.serve` is set, the QoS
        serving layer in front of it (shared by device and redis modes).

        Sets `self._executor` (the raw waist — internal maintenance traffic:
        lock watchdog renewals, eviction sweeps, durability flushes, which
        must never be shed or deadline-expired) and `self._dispatch` (what
        model getters bind to — the ServingLayer when configured, else the
        raw executor)."""
        from redisson_tpu.observability import ExecutorMetrics

        scfg = self.config.serve
        policy = None
        if scfg is not None:
            from redisson_tpu.serve import AdaptiveBatchPolicy, CostModel

            policy = AdaptiveBatchPolicy(
                CostModel(),
                max_linger_s=scfg.max_linger_s,
                target_batch_service_s=scfg.target_batch_service_s,
                min_batch_keys=scfg.min_batch_keys,
            )
        # Trace subsystem (trace/): built before the executor so every op —
        # including maintenance traffic — flows through the sampling hook;
        # the serving layer (below) picks it up off the executor for the
        # admission/retry annotations.
        self.trace = None
        trcfg = getattr(self.config, "trace", None)
        if trcfg is not None:
            from redisson_tpu.observability import register_trace
            from redisson_tpu.trace import TraceManager

            self.trace = TraceManager(trcfg, registry=self.metrics)
            register_trace(self.metrics, self.trace)
        kwargs = {}
        if max_batch_keys is not None:
            kwargs["max_batch_keys"] = max_batch_keys
        self._executor = CommandExecutor(
            backend, metrics=ExecutorMetrics(self.metrics), policy=policy,
            inflight_runs=getattr(self.config, "inflight_runs", 2),
            trace=self.trace,
            **kwargs)
        self.metrics.gauge("executor.queue_depth", self._executor.queue_depth)
        self.metrics.gauge(
            "executor.overlap_ratio",
            lambda: self._executor.pipeline_stats()["overlap_ratio"])
        if scfg is not None:
            from redisson_tpu.serve import ServingLayer

            self.serve = ServingLayer(self._executor, scfg,
                                      registry=self.metrics)
            self._dispatch = self.serve
        else:
            self.serve = None
            self._dispatch = self._executor

    def _make_resp_pool(self):
        """Connection pool to the configured redis endpoint — shared by
        passthrough traffic, blocking pops, coordination scripts and
        durability flushes (ConnectionPool.java role). With slave_addresses
        configured, a MasterSlaveRouter (write-to-master, balanced reads,
        freeze-driven promotion, MOVED/ASK redirects) wraps one pool per
        endpoint (MasterSlaveEntry.java:53-250)."""
        from urllib.parse import urlparse

        from redisson_tpu.interop.pool import RespConnectionPool

        rcfg = self.config.redis

        def factory(host: str, port: int) -> RespConnectionPool:
            return RespConnectionPool(
                host=host or "127.0.0.1",
                port=port or 6379,
                password=rcfg.password,
                db=rcfg.database,
                timeout=rcfg.timeout_ms / 1000.0,
                retry_attempts=rcfg.retry_attempts,
                retry_interval=rcfg.retry_interval_ms / 1000.0,
                size=rcfg.connection_pool_size,
                min_idle=rcfg.connection_minimum_idle_size,
                failed_attempts=rcfg.failed_attempts,
                reconnection_timeout=rcfg.reconnection_timeout_ms / 1000.0,
                idle_timeout=rcfg.idle_connection_timeout_ms / 1000.0,
            )

        u = urlparse(rcfg.address)
        if rcfg.cluster_addresses:
            from redisson_tpu.interop.topology_redis import (
                ClusterRouter, ClusterTopologyManager)

            router = ClusterRouter(factory, rcfg.cluster_addresses)
            mgr = ClusterTopologyManager(
                router,
                scan_interval_s=rcfg.cluster_scan_interval_ms / 1000.0)
            try:
                mgr.bootstrap()
            except Exception:
                # bootstrap dialed pools through the router; nobody above
                # holds a reference yet, so reclaim them (and the scan
                # thread) here or they leak per failed create().
                mgr.close()
                router.close()
                raise
            self._cluster_manager = mgr
            return router
        if rcfg.sentinel_addresses:
            from redisson_tpu.interop.resp_client import SyncPubSubClient
            from redisson_tpu.interop.topology_redis import SentinelManager

            def pubsub_factory(host: str, port: int) -> SyncPubSubClient:
                return SyncPubSubClient(
                    host=host, port=port, password=rcfg.password,
                    timeout=rcfg.timeout_ms / 1000.0)

            from redisson_tpu.interop.topology_redis import make_balancer

            return SentinelManager(
                factory, rcfg.sentinel_addresses, rcfg.master_name,
                read_mode=rcfg.read_mode, pubsub_factory=pubsub_factory,
                timeout=rcfg.timeout_ms / 1000.0,
                sentinel_password=rcfg.password,
                balancer=make_balancer(rcfg.load_balancer, rcfg.slave_weights,
                                       rcfg.default_slave_weight),
            )
        if rcfg.slave_addresses:
            from redisson_tpu.interop.topology_redis import (
                MasterSlaveRouter, RolePollingMonitor, make_balancer)

            router = MasterSlaveRouter(
                factory,
                f"{u.hostname or '127.0.0.1'}:{u.port or 6379}",
                rcfg.slave_addresses,
                read_mode=rcfg.read_mode,
                balancer=make_balancer(rcfg.load_balancer, rcfg.slave_weights,
                                       rcfg.default_slave_weight),
            )
            if rcfg.role_scan_interval_ms > 0:
                self._role_monitor = RolePollingMonitor(
                    router,
                    scan_interval_s=rcfg.role_scan_interval_ms / 1000.0,
                )
            return router
        pool = factory(u.hostname, u.port)
        return pool

    def _init_cluster_mode(self):
        from redisson_tpu.cluster import ClusterManager
        from redisson_tpu.observability import MetricsRegistry

        self._mode = "cluster"
        self.cluster = ClusterManager(self.config)
        # The router speaks the executor's narrow waist (execute_async /
        # execute_many / execute_sync / batch), so every model getter binds
        # to it unchanged — per-owner batch splitting and MOVED retries
        # happen below the models, like the reference's CommandAsyncService
        # hides slot routing from RBucket et al.
        self._dispatch = self._routing = self.cluster.router
        self._store = None
        self.metrics = MetricsRegistry()
        self.metrics.gauge("cluster.queue_depth",
                           self.cluster.router.queue_depth)
        # Per-shard subsystems (memstat / trace / serve / persist) live on
        # the shard clients — see ClusterManager.stats() for the rollup.
        self.memstat = None
        self._pressure = None
        self._memreport = None
        self.serve = None
        self.trace = None
        self._widths = (16, 32, 64, 128, 256)
        # Engine pub/sub and lock coordination are per-shard hubs; a
        # keyspace-wide topic surface needs a fan-out hub (future work), so
        # the facade declines rather than silently scoping to one shard.
        self._pubsub = None
        self._watchdog = None
        self._eviction = EvictionScheduler(self.cluster.router)
        self._remote_services = {}
        self._durability = None
        self._resp = None
        self._persist = None
        self._fault = None

    # -- CLUSTER command facade (cluster/; CLUSTER INFO/SLOTS/KEYSLOT) -------

    def _require_cluster(self, command: str):
        if self.cluster is None:
            raise RuntimeError(f"{command} requires Config.use_cluster()")
        return self.cluster

    def cluster_keyslot(self, key: str) -> int:
        """CLUSTER KEYSLOT analogue (hashtag-aware CRC16 slot)."""
        return self._require_cluster("CLUSTER KEYSLOT").cluster_keyslot(key)

    def cluster_slots(self):
        """CLUSTER SLOTS analogue: (start, end_inclusive, shard_id,
        replica_entries) ranges; each replica entry is {id, watermark, lag}
        for the owning shard's fleet, like redis lists replicas per range."""
        return self._require_cluster("CLUSTER SLOTS").cluster_slots()

    def cluster_info(self):
        """CLUSTER INFO analogue (cluster_state, slots_assigned, ...)."""
        return self._require_cluster("CLUSTER INFO").cluster_info()

    def _init_redis_mode(self):
        from redisson_tpu.interop.backend_redis import RedisBackend
        from redisson_tpu.observability import MetricsRegistry

        if self.config.persist is not None and self.config.persist.dir:
            raise NotImplementedError(
                "persist/ journals an engine-owned state tier; in redis "
                "passthrough mode the server owns the state (use the "
                "server's own AOF/RDB)")
        self._persist = None
        self._resp = self._make_resp_pool()
        try:
            self._resp.connect()
        except Exception:
            # Reclaim every background resource already started (the role
            # monitor thread would otherwise poll forever).
            if getattr(self, "_role_monitor", None) is not None:
                self._role_monitor.close()
                self._role_monitor = None
            if getattr(self, "_cluster_manager", None) is not None:
                self._cluster_manager.close()
                self._cluster_manager = None
            self._resp.close()  # reclaim the IO-loop thread
            raise
        self._backend = self._routing = RedisBackend(
            self._resp, hash_seed=getattr(self.config.redis, "hash_seed", 0))
        self._store = None
        self._widths = (16, 32, 64, 128, 256)
        # Passthrough mode holds no device state: the server owns memory
        # introspection (MEMORY USAGE et al. against the real server).
        self.memstat = None
        self._pressure = None
        self._memreport = None
        self.metrics = MetricsRegistry()
        self._build_executor(self._backend)
        # Observability for the blocking-pop silent-loss window (reply
        # window expires exactly as the server pops, or a mid-reply drop
        # forces a re-drive — r2 advisor finding): per-backend-instance so
        # two clients in one process never pool their counts.
        self.metrics.gauge("redis.blocking_pop_loss_windows",
                           lambda: self._backend.blocking_pop_loss_windows)
        # Engine-backed tiers are absent; coordination runs as server-side
        # Lua + pub/sub wake-ups instead (interop/coordination_redis.py) —
        # the reference's own execution model.
        self._pubsub = None
        self._watchdog = None
        # Redis-mode map caches register their Lua sweep here, so TTL
        # entries are physically removed without manual evict_expired calls
        # (the reference registers every map cache with EvictionScheduler,
        # RedissonMapCache.java:91-96; r2 advisor finding #3).
        self._eviction = EvictionScheduler()
        self._remote_services = {}
        self._durability = None
        from redisson_tpu.interop.coordination_redis import ScriptRunner

        self._redis_scripts = ScriptRunner(self._resp)
        self._redis_pubsub = None  # lazy: dedicated subscribe connection
        self._redis_watchdog = None  # lazy: lock lease renewal thread
        self._redis_coord_lock = _threading.Lock()

    def _redis_coordination(self):
        """(scripts, pubsub, watchdog) for redis-mode coordination objects;
        the subscribe connection and the renewal thread start on first use
        (the reference also dials pub/sub connections lazily,
        MasterSlaveConnectionManager.java:306-403)."""
        from urllib.parse import urlparse

        from redisson_tpu.interop.coordination_redis import RedisLockWatchdog
        from redisson_tpu.interop.resp_client import SyncPubSubClient

        with self._redis_coord_lock:
            if self._redis_pubsub is None:
                rcfg = self.config.redis
                u = urlparse(rcfg.address)
                # Follow master promotion: when a MasterSlaveRouter fronts
                # the endpoints, every (re)dial asks it for the current
                # master so lock wake-ups survive failover.
                addr_provider = None
                if getattr(self._resp, "master_address", None) is not None:
                    def addr_provider():
                        host, _, port = self._resp.master_address.rpartition(":")
                        return host, int(port)
                pubsub = SyncPubSubClient(
                    host=u.hostname or "127.0.0.1",
                    port=u.port or 6379,
                    password=rcfg.password,
                    timeout=rcfg.timeout_ms / 1000.0,
                    addr_provider=addr_provider,
                )
                try:
                    pubsub.connect()
                except Exception:
                    pubsub.close()  # reclaim the IO thread on a failed dial
                    raise
                self._redis_pubsub = pubsub
            if self._redis_watchdog is None:
                self._redis_watchdog = RedisLockWatchdog(self._redis_scripts)
            return self._redis_scripts, self._redis_pubsub, self._redis_watchdog

    def _connect_durability(self):
        from redisson_tpu.interop.durability import DurabilityManager

        self._resp = self._make_resp_pool()
        self._resp.connect()
        self._durability = DurabilityManager(
            self._store, self._resp,
            executor=self._executor, pod_backend=self._pod_backend(),
            hll_family=getattr(self._pod_backend(), "family", "m3"))
        if self.config.flush_interval_s > 0:
            self._durability.start_periodic(self.config.flush_interval_s)

    # -- durability / checkpoint --------------------------------------------

    @property
    def durability(self):
        """The DurabilityManager when a redis tier is configured, else None."""
        return self._durability

    @property
    def persist(self):
        """The PersistenceManager when Config.persist is set, else None."""
        return getattr(self, "_persist", None)

    @property
    def fault(self):
        """The FaultManager when Config.faults is set, else None."""
        return getattr(self, "_fault", None)

    @property
    def replicas(self):
        """The ReplicaManager when Config.replicas is set, else None."""
        return getattr(self, "_replicas", None)

    @property
    def geo(self):
        """The GeoManager when Config.geo is set, else None."""
        return getattr(self, "_geo", None)

    def wait_for_replicas(self, n: int, timeout_s: float = 5.0) -> int:
        """Redis WAIT analogue: block until n replicas have applied at
        least the primary's current committed journal seq; returns how
        many have (possibly < n on timeout)."""
        if self._replicas is None:
            raise RuntimeError("no replica fleet configured (Config.replicas)")
        return self._replicas.wait_for_replicas(n, timeout_s=timeout_s)

    def snapshot_now(self) -> str:
        """On-demand persistent snapshot (BGSAVE analogue): cuts through
        the dispatcher barrier, writes via checkpoint.py, truncates covered
        journal segments. Returns the snapshot directory."""
        if self._persist is None:
            raise RuntimeError("no persistence configured (Config.persist)")
        return self._persist.snapshot()

    def flush_to_redis(self, names=None) -> int:
        if self._durability is None:
            raise RuntimeError("no redis durability tier configured")
        return self._durability.flush(names)

    def _pod_backend(self):
        """The PodBackend when mode='pod' (it exposes bank_names), else None."""
        sketch = getattr(self._routing, "sketch", None) if self._routing else None
        return sketch if sketch is not None and hasattr(sketch, "bank_names") else None

    def save_checkpoint(self, path: str, names=None) -> int:
        """Snapshot sketch state to a local checkpoint directory. In pod
        mode, bank-resident HLL rows are exported (dispatcher-serialized)
        and saved alongside the store objects, so the flagship multi-chip
        state survives (VERDICT r1 item #5)."""
        from redisson_tpu import checkpoint

        self._require_store("checkpointing")
        extra = {}
        pod = self._pod_backend()
        if pod is not None:
            for n in pod.bank_names():
                if names is not None and n not in names:
                    continue
                exported = self._executor.execute_sync(n, "hll_export", None)
                if exported is not None:
                    regs, version = exported
                    extra[n] = ("hll", regs, {}, version)
            # Mesh-sharded bitsets/blooms live outside the store too (only
            # the pod backend has them; the single-chip TpuBackend also
            # passes the bank_names probe above but keeps bits in the store).
            for n in (pod.sharded_bits_names()
                      if hasattr(pod, "sharded_bits_names") else []):
                if names is not None and n not in names:
                    continue
                exported = self._executor.execute_sync(n, "bits_export", None)
                if exported is not None:
                    otype, host, meta, version = exported
                    extra[n] = (otype, host, meta, version)
        # Bloom barrier: host-mirror bits must reach device state before the
        # store snapshot reads it (same reason as the durability flush).
        from redisson_tpu.store import ObjectType

        for n in (names if names is not None else self._store.keys()):
            obj = self._store.get(n)
            if obj is not None and obj.otype == ObjectType.BLOOM:
                self._executor.execute_sync(n, "bloom_sync", None)
        return checkpoint.save(self._store, path, names, extra_objects=extra)

    def load_checkpoint(self, path: str, names=None) -> int:
        """Restore sketch state from a local checkpoint directory. HLLs are
        imported through the executor (pod mode: into bank rows; local/tpu:
        into the store) — checkpoints are portable across modes."""
        from redisson_tpu import checkpoint

        self._require_store("checkpointing")

        pod = self._pod_backend()

        def put(name, otype, host, meta) -> bool:
            if otype == "hll":
                self._executor.execute_sync(name, "hll_import", {"regs": host})
                if meta:
                    obj = self._store.get(name)
                    if obj is not None:
                        obj.meta.update(meta)
                return True
            if pod is not None and otype in ("bitset", "bloom"):
                # Pod mode: restore into a mesh-sharded array, not the
                # single-chip delegate store.
                self._executor.execute_sync(
                    name, "bits_import",
                    {"otype": otype, "array": host, "meta": meta})
                return True
            return False  # default store path

        restored = checkpoint.load(self._store, path, names, put=put)
        # Restore swaps state in UNDER the op path (store.swap), which the
        # epoch-stamped read cache and bloom host mirrors can't see — tell
        # the backend so stale cached reads/mirrors die with the old state.
        sketch = getattr(self._routing, "sketch", None)
        if sketch is not None and hasattr(sketch, "notify_restored"):
            for n in checkpoint.info(path).get("objects", {}):
                if names is not None and n not in names:
                    continue
                sketch.notify_restored(n)
        return restored

    def _require_store(self, feature: str) -> None:
        if self._store is None:
            raise NotImplementedError(
                f"{feature} needs a device-resident store; not available in "
                "redis passthrough mode")

    @classmethod
    def create(cls, config: Optional[Config] = None) -> "RedissonTPU":
        return cls(config)

    def _resolve_codec(self, codec):
        """Per-object codec: accepts a Codec instance or a registry name;
        falls back to the client default (Config.codec)."""
        return get_codec(codec) if codec is not None else self._codec

    # -- sketch objects (the TPU tier) --------------------------------------

    def get_hyper_log_log(self, name: str, codec=None) -> RHyperLogLog:
        return RHyperLogLog(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_bit_set(self, name: str) -> RBitSet:
        return RBitSet(name, self._dispatch, self._codec, self._widths)

    def get_bloom_filter(self, name: str, codec=None) -> RBloomFilter:
        return RBloomFilter(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def create_batch(self, **submit_kwargs) -> RBatch:
        """submit_kwargs (serving-layer mode: tenant= / timeout_s= /
        deadline=) budget the whole pipeline as one admission unit."""
        return RBatch(self._dispatch, self._codec, self._widths,
                      **submit_kwargs)

    # -- structure objects (the long-tail tier) -----------------------------

    def get_bucket(self, name: str, codec=None) -> RBucket:
        return RBucket(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_buckets(self, codec=None) -> RBuckets:
        return RBuckets(self._dispatch, self._resolve_codec(codec))

    def get_atomic_long(self, name: str) -> RAtomicLong:
        return RAtomicLong(name, self._dispatch, self._codec, self._widths)

    def get_atomic_double(self, name: str) -> RAtomicDouble:
        return RAtomicDouble(name, self._dispatch, self._codec, self._widths)

    def get_map(self, name: str, codec=None) -> RMap:
        return RMap(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_map_cache(self, name: str, codec=None) -> RMapCache:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisMapCache

            cache = RedisMapCache(name, self._redis_scripts, self._resolve_codec(codec))
            self._eviction.schedule(name, cache.evict_expired)
            return cache
        return RMapCache(
            name, self._dispatch, self._resolve_codec(codec), self._widths,
            eviction_scheduler=self._eviction,
        )

    def get_set(self, name: str, codec=None) -> RSet:
        return RSet(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_set_cache(self, name: str, codec=None) -> RSetCache:
        return RSetCache(
            name, self._dispatch, self._resolve_codec(codec), self._widths,
            eviction_scheduler=self._eviction,
        )

    def get_list(self, name: str, codec=None) -> RList:
        return RList(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_queue(self, name: str, codec=None) -> RQueue:
        return RQueue(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_deque(self, name: str, codec=None) -> RDeque:
        return RDeque(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_blocking_queue(self, name: str, codec=None) -> RBlockingQueue:
        return RBlockingQueue(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_blocking_deque(self, name: str, codec=None) -> RBlockingDeque:
        return RBlockingDeque(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_sorted_set(self, name: str, codec=None, key: Optional[Callable] = None) -> RSortedSet:
        return RSortedSet(
            name, self._dispatch, self._resolve_codec(codec), self._widths, key=key,
            guard_lock=self.get_lock(name + "__sortedset_guard"),
        )

    def get_scored_sorted_set(self, name: str, codec=None) -> RScoredSortedSet:
        return RScoredSortedSet(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_lex_sorted_set(self, name: str) -> RLexSortedSet:
        return RLexSortedSet(name, self._dispatch, self._codec, self._widths)

    def get_set_multimap(self, name: str, codec=None) -> RSetMultimap:
        return RSetMultimap(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_list_multimap(self, name: str, codec=None) -> RListMultimap:
        return RListMultimap(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_set_multimap_cache(self, name: str, codec=None):
        from redisson_tpu.models.multimap import RSetMultimapCache

        return RSetMultimapCache(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_list_multimap_cache(self, name: str, codec=None):
        from redisson_tpu.models.multimap import RListMultimapCache

        return RListMultimapCache(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_geo(self, name: str, codec=None) -> RGeo:
        return RGeo(name, self._dispatch, self._resolve_codec(codec), self._widths)

    def get_topic(self, name: str, codec=None) -> RTopic:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisTopic

            _, pubsub, _ = self._redis_coordination()
            return RedisTopic(name, self._resp, pubsub, self._resolve_codec(codec))
        return RTopic(name, self._dispatch, self._resolve_codec(codec), self._require_pubsub("topics"))

    def get_pattern_topic(self, pattern: str, codec=None) -> RPatternTopic:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisPatternTopic

            _, pubsub, _ = self._redis_coordination()
            return RedisPatternTopic(pattern, self._resp, pubsub, self._resolve_codec(codec))
        return RPatternTopic(pattern, self._dispatch, self._resolve_codec(codec), self._require_pubsub("topics"))

    # -- coordination -------------------------------------------------------

    def _require_pubsub(self, feature: str):
        if self._pubsub is None:
            raise NotImplementedError(
                f"{feature} needs the in-process engine's pub/sub hub, which "
                "this mode does not run")
        return self._pubsub

    def get_lock(self, name: str) -> RLock:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisLock

            scripts, pubsub, watchdog = self._redis_coordination()
            return RedisLock(name, scripts, pubsub, self.id, watchdog)
        return RLock(name, self._dispatch, self._require_pubsub("locks"), self.id, self._watchdog)

    def get_fair_lock(self, name: str) -> RFairLock:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisFairLock

            scripts, pubsub, watchdog = self._redis_coordination()
            return RedisFairLock(name, scripts, pubsub, self.id, watchdog)
        return RFairLock(name, self._dispatch, self._require_pubsub("locks"), self.id, self._watchdog)

    def get_read_write_lock(self, name: str) -> RReadWriteLock:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisReadWriteLock

            scripts, pubsub, watchdog = self._redis_coordination()
            return RedisReadWriteLock(name, scripts, pubsub, self.id, watchdog)
        return RReadWriteLock(name, self._dispatch, self._require_pubsub("locks"), self.id, self._watchdog)

    def get_multi_lock(self, *locks: RLock) -> RMultiLock:
        return RMultiLock(*locks)

    def get_semaphore(self, name: str) -> RSemaphore:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisSemaphore

            scripts, pubsub, _ = self._redis_coordination()
            return RedisSemaphore(name, scripts, pubsub)
        return RSemaphore(name, self._dispatch, self._require_pubsub("semaphores"))

    def get_count_down_latch(self, name: str) -> RCountDownLatch:
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisCountDownLatch

            scripts, pubsub, _ = self._redis_coordination()
            return RedisCountDownLatch(name, scripts, pubsub)
        return RCountDownLatch(name, self._dispatch, self._require_pubsub("latches"))

    def get_script(self):
        """Atomic scripting: python functions over the structure engine in
        local/tpu/pod mode (models/script.py), real server-side Lua
        (EVAL/EVALSHA) in redis mode (RedissonScript.java surface)."""
        if self._mode == "redis":
            from redisson_tpu.interop.coordination_redis import RedisScript

            return RedisScript(self._resp, self._codec)
        from redisson_tpu.models.script import RScript

        return RScript(self._dispatch)

    # -- bucket batch helpers (RedissonClient.java:174-192) -----------------

    def find_buckets(self, pattern: str):
        """Buckets whose names match the glob (reference findBuckets)."""
        return [self.get_bucket(n)
                for n in self.get_keys().get_keys_by_pattern(pattern)]

    def load_bucket_values(self, *keys):
        """name -> decoded value for existing keys (loadBucketValues);
        accepts names varargs or one iterable, like the reference's two
        overloads."""
        if len(keys) == 1 and not isinstance(keys[0], str):
            keys = tuple(keys[0])
        return self.get_buckets().get(*keys)

    def save_buckets(self, values) -> None:
        """Atomic multi-bucket MSET (saveBuckets)."""
        self.get_buckets().set(dict(values))

    # -- lifecycle / config introspection -----------------------------------

    def get_config(self) -> Config:
        """The live Config (reference getConfig)."""
        return self.config

    def is_shutdown(self) -> bool:
        return bool(getattr(self, "_is_shutdown", False))

    def is_shutting_down(self) -> bool:
        return bool(getattr(self, "_is_shutting_down", False))

    # -- observability ------------------------------------------------------

    def get_cluster_nodes_group(self):
        """Cluster-scoped health surface (reference getClusterNodesGroup);
        same node set — topology-specific nodes carry their role."""
        return self.get_nodes_group()

    def get_nodes_group(self):
        """Health/ping surface over compute devices + the redis tier
        (reference NodesGroup.pingAll, RedisNodes.java)."""
        from redisson_tpu.observability import NodesGroup

        return NodesGroup(self)

    def get_topology_manager(self, scan_interval_s: float = 1.0,
                             failed_attempts: int = 3):
        """Failure-detection poller pre-registered with this client's nodes
        (sentinel/cluster monitor analogue). Caller starts/stops it."""
        from redisson_tpu.parallel.topology import TopologyManager

        tm = TopologyManager(scan_interval_s, failed_attempts)
        for node in self.get_nodes_group().nodes():
            tm.add_node(node.ident, node.ping)
        return tm

    # -- services (L5b) -----------------------------------------------------

    def get_remote_service(self, name: str = "remote_service"):
        """RPC service registry/proxy factory (RRemoteService analogue).
        One cached instance per name; shut down with the client."""
        from redisson_tpu.services.remote import RRemoteService

        rs = self._remote_services.get(name)
        if rs is None:
            rs = self._remote_services[name] = RRemoteService(self, name)
        return rs

    def get_cache_manager(self, configs=None):
        """Spring-cache-manager analogue over RMap/RMapCache."""
        from redisson_tpu.services.cache_manager import CacheManager

        return CacheManager(self, configs)

    # -- keys facade (RKeys analogue) ---------------------------------------

    def get_keys(self) -> RKeys:
        return RKeys(self._dispatch, self._routing)

    def keys(self, pattern: str = "*"):
        return self._dispatch.execute_sync("", "keys", {"pattern": pattern})

    def flushall(self):
        # Routed through the executor so it serializes with in-flight ops on
        # the dispatcher thread (no mid-kernel store mutation).
        self._dispatch.execute_sync("", "flushall", None)

    def delete(self, name: str) -> bool:
        return self._dispatch.execute_sync(name, "delete", None)

    # -- memory facade (MEMORY command family; memstat/) ---------------------

    def _require_memreport(self, command: str):
        if self._memreport is None:
            raise RuntimeError(
                f"{command} requires a device-backed mode; in redis "
                "passthrough the server owns memory introspection")
        return self._memreport

    def memory_usage(self, name: str) -> Optional[int]:
        """MEMORY USAGE analogue: exact device bytes + metadata overhead
        for one key, or None when the key doesn't exist."""
        return self._require_memreport("MEMORY USAGE").memory_usage(name)

    def memory_stats(self):
        """MEMORY STATS analogue over the byte ledger."""
        return self._require_memreport("MEMORY STATS").memory_stats()

    def memory_doctor(self):
        """MEMORY DOCTOR analogue: rule-based findings dict."""
        return self._require_memreport("MEMORY DOCTOR").memory_doctor()

    def memory_verify(self):
        """Ledger invariant check: ledger totals vs. the sum of live
        Array.nbytes (zero drift when healthy)."""
        if self.memstat is None or self._store is None:
            raise RuntimeError("memory_verify requires a device-backed mode")
        sketch = getattr(self._routing, "sketch", self._routing)
        return self.memstat.verify(self._store, sketch)

    def info(self, section: Optional[str] = None):
        """INFO analogue: dict of section dicts (server, memory,
        persistence). `section` filters to one block, like INFO MEMORY."""
        sections = {
            "server": {"mode": self._mode, "client_id": str(self.id)},
        }
        if self._memreport is not None:
            sections["memory"] = self._memreport.info_memory()
        if getattr(self, "_persist", None) is not None:
            sections["persistence"] = self._persist.stats()
        replication = None
        if getattr(self, "_geo", None) is not None:
            replication = self._geo.info()
        elif getattr(self, "_replicas", None) is not None:
            replication = {"role": "primary"}
        if replication is not None:
            if getattr(self, "_replicas", None) is not None:
                replication["connected_replicas"] = len(
                    self._replicas.replicas)
            sections["replication"] = replication
        if self.cluster is not None:
            sections["cluster"] = self.cluster.cluster_info()
        if section is not None:
            key = section.lower()
            if key not in sections:
                raise ValueError(f"unknown INFO section '{section}'")
            return {key: sections[key]}
        return sections

    # -- lifecycle ----------------------------------------------------------

    def shutdown(self):
        self._is_shutting_down = True
        try:
            self._shutdown_inner()
        finally:
            # Flags must flip even when a teardown step raises — a client
            # permanently reporting "shutting down" would wedge callers.
            self._is_shutting_down = False
            self._is_shutdown = True

    def _shutdown_inner(self):
        if getattr(self, "wire", None) is not None:
            # Wire first, in every mode: stop accepting sockets and drain
            # the event loop before the dispatch stack underneath (serve /
            # executor / shard clients) starts rejecting its submissions.
            try:
                self.wire.stop()
            except Exception:
                pass
            self.wire = None
        if getattr(self, "cluster", None) is not None:
            # Cluster facade: the shard clients own every background
            # resource; the manager closes the router (its redirect worker)
            # then shuts each shard down through this same path.
            if self._eviction is not None:
                self._eviction.shutdown()
            self.cluster.shutdown()
            self.cluster = None
            return
        if getattr(self, "_fault", None) is not None:
            # First: stop the watchdog (it reads executor internals) and
            # wait out in-flight rebuilds while the executor still accepts
            # the replay traffic they submit.
            try:
                self._fault.stop()
            except Exception:
                pass
            self._fault = None
        if getattr(self, "_geo", None) is not None:
            # Geo site before replicas/persist: link threads read this
            # journal and peer appliers dispatch into this executor; both
            # must quiesce (and the LWW sidecar flush) while the stack
            # under them still accepts work.
            try:
                self._geo.close()
            except Exception:
                pass
            self._geo = None
        if getattr(self, "_replicas", None) is not None:
            # Replica fleet next: the prober must stop before the executor
            # it polls drains, and each replica shuts its own client down
            # (the promoted one tears its attached persistence down too).
            try:
                self._replicas.close()
            except Exception:
                pass
            self._replicas = None
        if getattr(self, "_persist", None) is not None:
            # Phase 1: stop the snapshotter before the executor drains (a
            # barrier cut submitted after shutdown would never dispatch);
            # the journal stays attached so drained ops still journal.
            self._persist.stop_background()
        for rs in self._remote_services.values():
            try:
                rs.shutdown(wait=False)
            except Exception:
                pass
        self._remote_services.clear()
        if self._durability is not None:
            self._durability.stop_periodic()
            try:
                self._durability.flush()  # final flush on clean shutdown
            except Exception:
                pass
            self._durability = None
        if getattr(self, "_role_monitor", None) is not None:
            self._role_monitor.close()
            self._role_monitor = None
        if getattr(self, "_cluster_manager", None) is not None:
            self._cluster_manager.close()
            self._cluster_manager = None
        if getattr(self, "_redis_watchdog", None) is not None:
            self._redis_watchdog.shutdown()
            self._redis_watchdog = None
        if getattr(self, "_redis_pubsub", None) is not None:
            try:
                self._redis_pubsub.close()
            except Exception:
                pass
            self._redis_pubsub = None
        if self._resp is not None:
            try:
                self._resp.close()
            except Exception:
                # A wedged IO loop must not abort the rest of shutdown.
                pass
            self._resp = None
        if self._eviction is not None:
            self._eviction.shutdown()
        if self._watchdog is not None:
            self._watchdog.shutdown()
        if getattr(self, "serve", None) is not None:
            # Closes the retry timer first (pending retries resolve their
            # outer futures with CancelledError), then the executor itself.
            self.serve.shutdown()
        else:
            self._executor.shutdown()
        if getattr(self, "_persist", None) is not None:
            # Phase 2: executor drained — every dispatched op has journaled;
            # final flush + fsync, then release the segment files.
            try:
                self._persist.close()
            except Exception:
                pass
            self._persist = None
        sketch = getattr(getattr(self, "_routing", None), "sketch", None)
        completer = getattr(sketch, "completer", None)
        if completer is not None:
            # Resolve every future whose device result is still in flight
            # before tearing the rest down (the dispatcher only dispatches;
            # materialization happens on the completer thread).
            completer.shutdown()
        if getattr(self._routing, "structures", None) is not None:
            # Dispatcher has exited: release threads parked in blocking pops.
            self._routing.structures.fail_waiters()
        if self._pubsub is not None:
            self._pubsub.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
