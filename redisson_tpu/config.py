"""Config system — typed modes + JSON/YAML load/save.

Mirrors the reference's `Config.java` (515 LoC) + `ConfigSupport.java`
(Jackson JSON/YAML): a top-level Config holding exactly one server-mode
section. Our modes map the reference's five connection managers
(`Redisson.java:96-120`) onto the TPU world:

  * local   — in-process pure-python backend (useSingleServer analogue for
              tests / the long-tail objects).
  * tpu     — single-chip sketch engine (the north-star backend).
  * pod     — multi-chip mesh-sharded sketch engine (useClusterServers
              analogue; shards by slot across devices).
  * redis   — passthrough to a real Redis via the RESP client (durability /
              interop tier).

Knobs follow `BaseConfig.java:27-86` where they translate (timeouts, retry
policy) and add the TPU-specific batching knobs (SURVEY.md §7 step 3).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class LocalConfig:
    """In-process backend (no device)."""


@dataclass
class TpuConfig:
    """Single-chip sketch engine."""

    device_index: int = 0
    hll_impl: str = "scatter"  # "scatter" | "sort"; scatter ~2x faster at 1M-key batches on v5e (ops/hll.py)
    # HLL hash family: "murmur3" (framework-native murmur3 x64 128) or
    # "redis" (MurmurHash64A seed 0xadc83b19, exactly redis hyperloglog.c
    # hllPatLen) — choose "redis" when flushed sketches must stay
    # server-mergeable under later server-side PFADDs (mixed writers).
    hll_hash: str = "murmur3"
    # Ingest path. "auto" follows the link rule (backend_tpu.ingest_rule):
    # a batch of at least 65,536 keys folds on the host when the native
    # library is loaded and the measured link makes a folded plane cheaper
    # to ship than the raw keys; everything else inserts on the device
    # with hll_impl. The rest force one path: "device" ships raw keys
    # (8 B/key) and inserts with the configured hll_impl; "scatter" /
    # "sort" / "segment" force that device insert kernel (segment = the
    # Pallas segmented-scatter); "hostfold" folds into a 16 KB sketch
    # natively and ships that; "delta" folds hll_add/bloom_add/bitset_set
    # batches into per-target delta planes on the host and retires every
    # plane staged in one pipeline window through a single fused device
    # merge (README "Delta ingest").
    ingest: str = "auto"
    hash_seed: int = 0
    # Coalescing cap for one dispatcher run. Device kernels still chunk at
    # engine.MAX_BUCKET (2^21) per call; a larger run amortizes per-run
    # costs (host fold setup, changed-readback) — measured on v5e: 2M cap
    # 149M inserts/s, 8M cap 174M/s, 32M slightly worse (latency).
    max_batch_keys: int = 1 << 23
    key_width_buckets: tuple = (16, 32, 64, 128, 256)
    # Epoch-stamped read cache: memoized hll_count / BITCOUNT / bloom
    # contains results per (target, write-epoch) — the client-side-caching
    # analogue. Capacity in entries; 0 disables.
    read_cache_entries: int = 1024


@dataclass
class PodConfig(TpuConfig):
    """Mesh-sharded sketch engine across all visible devices."""

    mesh_axis: str = "shards"
    num_shards: int = 0  # 0 = all devices
    bank_capacity: int = 4096  # sketch rows in the sharded bank


@dataclass
class RedisConfig:
    """RESP passthrough / durability flush target."""

    address: str = "redis://127.0.0.1:6379"
    # Master/slave topology (BaseMasterSlaveServersConfig): writes go to
    # `address`, reads balance over `slave_addresses` per `read_mode`
    # (SLAVE | MASTER | MASTER_SLAVE). Empty = single endpoint.
    slave_addresses: List[str] = dataclasses.field(default_factory=list)
    read_mode: str = "SLAVE"
    # Slave read balancing (reference `connection/balancer/`):
    # "round_robin" | "random" | "weighted" (weighted uses slave_weights,
    # address -> weight, with default_slave_weight for unlisted addresses).
    load_balancer: str = "round_robin"
    slave_weights: Dict[str, int] = dataclasses.field(default_factory=dict)
    default_slave_weight: int = 1
    # Cluster mode (ClusterServersConfig): bootstrap the slot topology with
    # CLUSTER NODES from any of these seeds, route keyed commands by CRC16
    # slot, and re-scan every cluster_scan_interval_ms (the reference's
    # scanInterval; 0 = bootstrap only). Takes precedence over sentinel and
    # master/slave settings.
    cluster_addresses: List[str] = dataclasses.field(default_factory=list)
    cluster_scan_interval_ms: int = 1000
    # Sentinel mode (SentinelServersConfig): discover the master/slaves by
    # name from these sentinels and follow +switch-master events. When set,
    # `address`/`slave_addresses` are ignored.
    sentinel_addresses: List[str] = dataclasses.field(default_factory=list)
    master_name: str = "mymaster"
    # Elasticache-style detection (ElasticacheServersConfig.scanInterval):
    # poll INFO replication roles every N ms (0 = off); needs
    # slave_addresses. Catches AWS-side promotions no sentinel announces.
    role_scan_interval_ms: int = 0
    # Murmur3 seed for wire-mode bloom index derivation; MUST match the
    # TPU tier's TpuConfig.hash_seed when filters cross tiers via
    # durability flushes (indexes are bit-compatible only at equal seeds).
    hash_seed: int = 0
    timeout_ms: int = 3000  # BaseConfig.timeout
    retry_attempts: int = 3  # BaseConfig.retryAttempts
    retry_interval_ms: int = 1000  # BaseConfig.retryInterval
    password: Optional[str] = None
    database: int = 0
    # Connection pool (connection/pool/ConnectionPool.java semantics):
    connection_pool_size: int = 4  # masterConnectionPoolSize
    connection_minimum_idle_size: int = 1  # masterConnectionMinimumIdleSize
    failed_attempts: int = 3  # freeze threshold (ConnectionPool.java:184-186)
    reconnection_timeout_ms: int = 3000  # re-probe period (:297-386)
    idle_connection_timeout_ms: int = 10000  # reaper (IdleConnectionWatcher)


@dataclass
class ServeConfig:
    """QoS serving layer (redisson_tpu/serve/) in front of the executor.

    Orthogonal to the backend mode (like flush_interval_s): any compute
    tier can sit behind admission control. Maps the reference's L2 knobs —
    `retryAttempts`, `retryInterval`, `timeout` (BaseConfig.java:27-86) —
    plus the admission/batching knobs the reference lacks (see PARITY.md).
    """

    # -- admission ----------------------------------------------------------
    # Per-tenant token-bucket rate in keys/sec (0 = unlimited). A tenant is
    # whatever string the caller passes ("" = the default tenant);
    # tenant_rates/tenant_bursts override per name.
    default_tenant_rate: float = 0.0
    default_tenant_burst: float = 0.0  # 0 = one second's worth of rate
    tenant_rates: Dict[str, float] = field(default_factory=dict)
    tenant_bursts: Dict[str, float] = field(default_factory=dict)
    # Bounded global queue: shed on depth high-watermark, or once the cost
    # model estimates queueing delay past the budget (0 = depth-only).
    max_queue_ops: int = 10000
    max_queue_delay_s: float = 0.0
    # -- adaptive batching --------------------------------------------------
    max_linger_s: float = 0.002  # hold a batch open at most this long
    target_batch_service_s: float = 0.005  # size batches to this service time
    min_batch_keys: int = 4096
    # -- deadlines / retry / breaker (reference BaseConfig analogues) -------
    default_timeout_ms: int = 3000  # BaseConfig.timeout; 0 = no deadline
    retry_attempts: int = 3  # BaseConfig.retryAttempts (retries, not tries)
    retry_interval_ms: int = 50  # BaseConfig.retryInterval (base backoff)
    breaker_failure_threshold: int = 5
    breaker_reset_timeout_ms: int = 1000
    breaker_half_open_probes: int = 1


@dataclass
class PersistConfig:
    """Durability subsystem (redisson_tpu/persist/): write-ahead op journal
    + background snapshots + crash recovery. Orthogonal to the backend mode
    (any engine-owned tier persists; redis passthrough mode has no
    client-side state to persist and rejects this section)."""

    # Journal + snapshot directory ("" disables persistence even when the
    # section is present — lets configs toggle without deleting it).
    dir: str = ""
    # appendfsync analogue: "always" (group-committed write-ahead fsync,
    # durability lag bounded by the pipeline window), "everysec"
    # (background fsync every fsync_interval_s), "off" (OS-paced).
    fsync: str = "everysec"
    fsync_interval_s: float = 1.0
    # Group-commit size for fsync="always"; 0 = follow Config.inflight_runs
    # (one fsync per pipeline window). 1 = strict fsync-per-run.
    group_commit_runs: int = 0
    segment_max_bytes: int = 64 << 20
    # Background BGSAVE cadence (0 = on-demand via client.persist.snapshot()
    # only). Each snapshot truncates wholly-covered journal segments.
    snapshot_interval_s: float = 0.0
    snapshot_keep: int = 2
    # Replay snapshot + journal suffix automatically at client create when
    # the directory holds prior state.
    auto_recover: bool = True


@dataclass
class FaultConfig:
    """Fault subsystem (redisson_tpu/fault/): classification is always on
    (the classify boundary has no knob — raw device errors never reach
    futures); this section controls injection, the run watchdog, and the
    self-healing rebuild path."""

    # Declarative injection schedule: list of FaultRule dicts
    # ({"seam": ..., "fault": ..., "nth": ..., "times": ..., "kind": ...,
    # "target": ...}) — empty = no injection (production default).
    plan: List[Dict[str, Any]] = field(default_factory=list)
    seed: int = 0  # documents how a random plan was generated
    # Run watchdog over the executor's in-flight window.
    watchdog: bool = False
    watchdog_margin: float = 8.0  # x the cost model's EWMA estimate
    watchdog_floor_s: float = 2.0  # never trip faster than this
    watchdog_poll_s: float = 0.05
    # Self-healing HBM rebuild on StateUncertain/DeviceLost retirement.
    # Needs Config.persist for host truth; without it, faulted targets
    # degrade to read-only immediately.
    rebuild: bool = True


@dataclass
class TraceConfig:
    """Trace subsystem (redisson_tpu/trace/): sampled per-op spans,
    quantile latency histograms, SLOWLOG/MONITOR/LATENCY parity surfaces
    and Chrome-trace export. Orthogonal to the backend mode; the <1%
    overhead budget holds at the default sampling stride."""

    # Sample 1 op in `sample_every` (deterministic counter stride seeded
    # by `seed`); 1 = trace everything (tests/debugging only).
    sample_every: int = 128
    seed: int = 0
    # Bounded ring of finished spans kept for chrome_trace() export.
    ring: int = 4096
    # SLOWLOG analogue: ops slower than this land in a bounded ring with
    # their per-stage breakdown (redis slowlog-log-slower-than is 10ms).
    slowlog_threshold_ms: float = 10.0
    slowlog_max_len: int = 128
    # Per-subscriber MONITOR queue bound; full queues drop-and-count.
    monitor_queue: int = 1024
    # LATENCY HISTORY analogue: per-stage spikes above this threshold.
    latency_threshold_ms: float = 100.0
    latency_history_len: int = 160


@dataclass
class MemConfig:
    """Memory watermarks and pressure behavior (memstat/).

    The byte ledger itself is always on; this section only configures
    the pressure gate (maxmemory analogue). high_watermark_bytes == 0
    disables shedding entirely."""

    # Shed memory-growing writes at/above this total (0 = never shed).
    high_watermark_bytes: int = 0
    # Hysteresis: once shedding, resume writes only below this (0 =>
    # same as high_watermark_bytes, i.e. no hysteresis band).
    low_watermark_bytes: int = 0
    # Count cache/scratch/staging meters toward the watermark total.
    include_overhead: bool = True
    # Growth-rate EWMA halflife for the time-to-watermark forecast.
    ewma_halflife_s: float = 30.0
    # retry-after hint attached to shed RejectedErrors.
    retry_after_s: float = 1.0
    # Meter sampling throttle on the admission path (seconds).
    meter_refresh_s: float = 0.05
    # MEMORY DOCTOR warns when usage exceeds this fraction of the
    # high-watermark.
    doctor_watermark_ratio: float = 0.9


@dataclass
class ClusterConfig:
    """Slot-sharded namespace (redisson_tpu/cluster/): N full engine stacks
    each owning contiguous ranges of the 16384 CRC16 slots, fronted by a
    ClusterRouter that splits batches per owner and handles MOVED/ASK
    redirects — the engine-owned analogue of ClusterServersConfig /
    `ClusterConnectionManager.java`. Orthogonal to the per-shard compute
    mode: each shard runs the Config's compute section (local by default;
    tpu spreads shards round-robin across visible devices). Live slot
    migration (`client.cluster.migrate_slots`) requires `dir` so each shard
    journals."""

    num_shards: int = 4
    # Root persist directory; each shard journals under <dir>/shard-NN.
    # "" = no per-shard persistence (migration unavailable).
    dir: str = ""
    fsync: str = "off"
    # Per-shard admission control: front every shard with a ServingLayer
    # built from Config.serve (which must then be present).
    shard_serve: bool = False
    # MOVED redirect retry depth before an op's future fails.
    redirect_retries: int = 5
    # Shard-level HA: each shard gets its own replica fleet tailing the
    # shard journal (requires `dir`), with per-shard bounded-staleness read
    # routing and fence-first automatic failover — the per-partition slave
    # set of ClusterConnectionManager.java. Replica tuning knobs (staleness
    # bounds, probe cadence, ...) inherit from Config.replicas when that
    # section is set on the facade config.
    replicas_per_shard: int = 0
    # Quarantine-then-migrate on topology node_down events (parallel/
    # topology.py watcher): drain the lost shard's slots onto survivors.
    auto_heal: bool = True
    # Shard DATA plane. "stacks" (default): N full engine stacks, one
    # executor/dispatcher/backend per shard. "mesh": N LOGICAL shards share
    # ONE executor and ONE backend whose HLL bank is row-sharded across a
    # device mesh (parallel/mesh.ShardedBank); cross-shard PFMERGE/count
    # run as shard_map collectives instead of export->host-fold->import,
    # and a multi-shard pipeline window retires in a single fused launch.
    # Slot ownership, MOVED/ASK generation, journaling order, and migration
    # semantics are bit-identical between the two planes.
    data_plane: str = "stacks"
    # INTERNAL: >= 0 marks a config built by the ClusterManager for one
    # shard member (installs the slot-ownership guard); users leave it -1.
    # -2 marks the SHARED engine client of the mesh data plane (installs
    # the MeshOwnershipBackend guard, never the cluster facade).
    shard_id: int = -1


@dataclass
class ReplicaConfig:
    """Read-replica fleet (redisson_tpu/replica/): N serving replicas, each
    a full engine stack tailing the primary's journal, fronted by a
    ReplicaRouter that sends read-only op kinds to a replica whose applied
    watermark satisfies the read's staleness bound — the engine-owned
    analogue of `readMode=SLAVE` in `MasterSlaveConnectionManager.java`.
    Requires `Config.persist` with a dir (replicas tail that journal)."""

    num_replicas: int = 2
    # Bounded-staleness defaults; per-read `max_lag=`/`max_lag_s=` override.
    # A replica is eligible when primary_seq - applied_seq <= max_lag_seqs
    # AND (max_lag_s == 0 or time since it was last caught up <= max_lag_s).
    max_lag_seqs: int = 1024
    max_lag_s: float = 0.0
    # Pin a tenant's reads at/above the highest journal seq acked to it.
    read_your_writes: bool = True
    # Follower tail cadence / apply batch (JournalFollower knobs).
    poll_interval_s: float = 0.01
    apply_window: int = 1024
    # Failover: promote the highest-watermark replica when the primary
    # dies (DeviceLostFault through the fault manager, or health_failures
    # consecutive failed probes at health_interval_s cadence).
    auto_failover: bool = True
    health_interval_s: float = 0.25
    health_failures: int = 3
    promote_timeout_s: float = 30.0


@dataclass
class GeoConfig:
    """Active-active geo-replication (redisson_tpu/geo/): this site is one
    of N independent full engine stacks ("sites") that each accept local
    writes and asynchronously converge. The persist journal IS the
    replication transport (exactly as it is for `replica/`): per-peer
    SiteLinks tail the local journal, fold the sketch-tier write stream
    into stamped delta planes, and ship them; the receiving site applies
    them through the fused delta merge path as `geo_*` op kinds.
    Requires `Config.persist` with a dir and the native fold library
    (same precondition as ingest='delta'). Peering is wired at runtime
    with `geo.connect_sites([...])` / `client.geo.connect(peers)` — the
    config names the site and tunes the link/anti-entropy cadence."""

    # Unique site name in the fleet ("" = derived from the client id).
    # Stamps are (origin_seq, site_id); ties break on the id string, so
    # give sites stable, distinct names.
    site_id: str = ""
    # Link tail cadence + max journal records folded per poll batch.
    poll_interval_s: float = 0.01
    batch_records: int = 4096
    # Anti-entropy cadence: version-vector exchange (peer-restart rewind),
    # JournalGap snapshot repair, and sidecar meta persistence.
    anti_entropy_interval_s: float = 0.5
    # Bound on unresolved remote-apply futures tracked per applier (the
    # convergence watermark window; older entries are dropped once done).
    apply_window: int = 4096


@dataclass
class WireConfig:
    """RESP2/RESP3 network front-end (redisson_tpu/wire/): a TCP server
    real redis clients (redis-cli, redis-py, Redisson) connect to; pipelined
    commands from all connections coalesce into `ServingLayer.execute_many`
    windows. In cluster mode one server fronts every shard (base `port` + i,
    or all-ephemeral when port=0) and keyed commands answer real -MOVED/-ASK
    redirects during live slot migration."""

    host: str = "127.0.0.1"
    # 0 = bind an ephemeral port (read it back from client.wire.port).
    port: int = 0
    # Require AUTH/HELLO AUTH before any other command (None = open).
    password: Optional[str] = None
    # Accept-time shed bound: further connections get -BUSY + close
    # (0 = unlimited).
    max_connections: int = 1024
    # Per-connection pipelined command cap: commands past this many
    # unanswered get -BUSY in their reply position (RejectedError shape).
    max_inflight_per_conn: int = 128
    # Listen backlog handed to the OS.
    backlog: int = 128
    # retry-after hint rendered into wire -BUSY sheds.
    shed_retry_after_s: float = 0.05


@dataclass
class Config:
    local: Optional[LocalConfig] = None
    tpu: Optional[TpuConfig] = None
    pod: Optional[PodConfig] = None
    redis: Optional[RedisConfig] = None
    # QoS serving layer (None = raw executor, the seed behavior).
    serve: Optional[ServeConfig] = None
    # Durability subsystem (None = no journal/snapshots, the seed behavior).
    persist: Optional[PersistConfig] = None
    # Fault subsystem (None = classify-only; no injection/watchdog/rebuild).
    faults: Optional[FaultConfig] = None
    # Trace subsystem (None = no spans/slowlog/monitor, the seed behavior).
    trace: Optional[TraceConfig] = None
    # Memory watermarks/pressure (None = ledger only, never shed).
    memory: Optional[MemConfig] = None
    # Slot-sharded cluster tier (None = one engine owns all slots).
    cluster: Optional[ClusterConfig] = None
    # Read-replica fleet (None = primary serves all reads).
    replicas: Optional[ReplicaConfig] = None
    # RESP wire front-end (None = facade-only access, no TCP listener).
    wire: Optional[WireConfig] = None
    # Active-active geo-replication (None = this engine is not a site).
    geo: Optional[GeoConfig] = None
    # Durability: flush sketch state to redis every N seconds (0 = off).
    flush_interval_s: float = 0.0
    codec: str = "json"  # default value codec, reference Config.java:53-55
    threads: int = 0  # 0 => cpu_count, reference Config.java:50
    # Executor pipeline depth: how many coalesced runs may be in flight at
    # once (staged + dispatched, futures unresolved). 1 = the serial seed
    # behavior; 2-4 overlaps host staging with device compute (the Netty
    # channel-pipelining analogue). Per-target ordering is preserved at any
    # depth.
    inflight_runs: int = 2

    _MODES = ("local", "tpu", "pod", "redis")

    def mode(self) -> str:
        """The single active backend mode (validated)."""
        active = [m for m in self._MODES if getattr(self, m) is not None]
        # redis may coexist with any compute mode as the durability tier.
        compute = [m for m in active if m != "redis"]
        if len(compute) > 1:
            raise ValueError(f"multiple backend modes configured: {active}")
        if compute:
            return compute[0]
        if not active:
            return "local"
        return active[0]

    def use_local(self) -> "LocalConfig":
        self.local = self.local or LocalConfig()
        return self.local

    def use_tpu(self) -> "TpuConfig":
        self.tpu = self.tpu or TpuConfig()
        return self.tpu

    def use_pod(self) -> "PodConfig":
        self.pod = self.pod or PodConfig()
        return self.pod

    def use_redis(self) -> "RedisConfig":
        self.redis = self.redis or RedisConfig()
        return self.redis

    def use_serve(self) -> "ServeConfig":
        self.serve = self.serve or ServeConfig()
        return self.serve

    def use_persist(self, dir: str = "") -> "PersistConfig":
        self.persist = self.persist or PersistConfig()
        if dir:
            self.persist.dir = dir
        return self.persist

    def use_faults(self) -> "FaultConfig":
        self.faults = self.faults or FaultConfig()
        return self.faults

    def use_trace(self) -> "TraceConfig":
        self.trace = self.trace or TraceConfig()
        return self.trace

    def use_memstat(self) -> "MemConfig":
        self.memory = self.memory or MemConfig()
        return self.memory

    def use_cluster(self, num_shards: int = 0, dir: str = "",
                    replicas_per_shard: int = 0,
                    data_plane: str = "") -> "ClusterConfig":
        self.cluster = self.cluster or ClusterConfig()
        if num_shards:
            self.cluster.num_shards = num_shards
        if dir:
            self.cluster.dir = dir
        if replicas_per_shard:
            self.cluster.replicas_per_shard = replicas_per_shard
        if data_plane:
            if data_plane not in ("stacks", "mesh"):
                raise ValueError(
                    f"cluster.data_plane must be 'stacks' or 'mesh', "
                    f"got {data_plane!r}")
            self.cluster.data_plane = data_plane
        return self.cluster

    def use_replicas(self, num_replicas: int = 0) -> "ReplicaConfig":
        self.replicas = self.replicas or ReplicaConfig()
        if num_replicas:
            self.replicas.num_replicas = num_replicas
        return self.replicas

    def use_wire(self, host: str = "", port: int = -1) -> "WireConfig":
        self.wire = self.wire or WireConfig()
        if host:
            self.wire.host = host
        if port >= 0:
            self.wire.port = port
        return self.wire

    def use_geo(self, site_id: str = "") -> "GeoConfig":
        self.geo = self.geo or GeoConfig()
        if site_id:
            self.geo.site_id = site_id
        return self.geo

    # -- (de)serialization (ConfigSupport.java analogue) --------------------

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for f in dataclasses.fields(self):
            if f.name.startswith("_"):
                continue
            v = getattr(self, f.name)
            if v is None:
                continue
            out[f.name] = dataclasses.asdict(v) if dataclasses.is_dataclass(v) else v
        return out

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    def to_yaml(self) -> str:
        import yaml

        return yaml.safe_dump(self.to_dict())

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Config":
        kwargs: Dict[str, Any] = {}
        section_types = {
            "local": LocalConfig,
            "tpu": TpuConfig,
            "pod": PodConfig,
            "redis": RedisConfig,
            "serve": ServeConfig,
            "persist": PersistConfig,
            "faults": FaultConfig,
            "trace": TraceConfig,
            "memory": MemConfig,
            "cluster": ClusterConfig,
            "replicas": ReplicaConfig,
            "wire": WireConfig,
            "geo": GeoConfig,
        }
        for key, value in d.items():
            sec = section_types.get(key)
            if sec is not None:
                value = dict(value)
                if "key_width_buckets" in value:
                    value["key_width_buckets"] = tuple(value["key_width_buckets"])
                kwargs[key] = sec(**value)
            else:
                kwargs[key] = value
        return cls(**kwargs)

    @classmethod
    def from_json(cls, text: str) -> "Config":
        return cls.from_dict(json.loads(text))

    @classmethod
    def from_yaml(cls, text: str) -> "Config":
        import yaml

        return cls.from_dict(yaml.safe_load(text))
