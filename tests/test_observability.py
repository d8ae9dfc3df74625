"""Observability tests: metrics registry, executor instrumentation,
nodes/health API."""

import pytest

from redisson_tpu.client import RedissonTPU
from redisson_tpu.observability import Histogram, MetricsRegistry, NodesGroup


@pytest.fixture()
def client():
    c = RedissonTPU.create()
    yield c
    c.shutdown()


def test_registry_counters_and_gauges():
    r = MetricsRegistry()
    r.inc("a.b")
    r.inc("a.b", 4)
    assert r.counter("a.b") == 5
    r.gauge("g", lambda: 7.5)
    snap = r.snapshot()
    assert snap["counters"]["a.b"] == 5
    assert snap["gauges"]["g"] == 7.5


def test_histogram_stats():
    h = Histogram()
    for v in (0.001, 0.01, 0.01, 1.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 4
    assert s["min"] == 0.001 and s["max"] == 1.0
    assert abs(s["mean"] - (0.001 + 0.01 + 0.01 + 1.0) / 4) < 1e-9


def test_prometheus_rendering():
    r = MetricsRegistry()
    r.inc("ops.total", 3)
    r.gauge("queue.depth", lambda: 2)
    r.observe("lat", 0.005)
    text = r.render_prometheus()
    assert "ops_total 3" in text
    assert "queue_depth 2" in text
    assert "lat_count 1" in text
    assert 'lat_bucket{le="0.01"}' in text


def test_prometheus_histogram_exposition():
    r = MetricsRegistry()
    r.observe("lat", 0.005)
    r.observe("lat", 2.0)
    text = r.render_prometheus()
    assert "# TYPE lat histogram" in text
    # The overflow bucket must be spelled +Inf, never Python's "inf".
    assert 'lat_bucket{le="+Inf"} 2' in text
    assert 'le="inf"' not in text
    counts = [int(line.rsplit(" ", 1)[1])
              for line in text.splitlines() if line.startswith("lat_bucket")]
    assert counts == sorted(counts)  # cumulative => non-decreasing
    assert counts[-1] == 2  # +Inf bucket equals _count
    assert "lat_count 2" in text
    assert "lat_sum" in text


def test_registry_snapshot_mutation_safe():
    r = MetricsRegistry()
    r.inc("ops.total", 3)
    r.observe("lat", 0.005)
    snap = r.snapshot()
    snap["counters"]["ops.total"] = 999
    snap["histograms"]["lat"]["buckets"].clear()
    snap["histograms"]["lat"]["count"] = 0
    snap2 = r.snapshot()
    assert snap2["counters"]["ops.total"] == 3
    assert snap2["histograms"]["lat"]["count"] == 1
    assert sum(snap2["histograms"]["lat"]["buckets"].values()) == 1


def test_fault_injector_snapshot_mutation_safe():
    from redisson_tpu.fault.inject import FaultInjector, FaultPlan, FaultRule

    inj = FaultInjector(FaultPlan(rules=[FaultRule(seam="journal_fsync")]))
    with pytest.raises(Exception):
        inj.fire("journal_fsync")
    snap = inj.snapshot()
    snap["fired"][0]["seam"] = "corrupted"
    snap["hits"][0] = 999
    snap2 = inj.snapshot()
    assert snap2["fired"][0]["seam"] == "journal_fsync"
    assert snap2["hits"][0] == 1


def test_executor_metrics_flow(client):
    h = client.get_hyper_log_log("obs:h")
    h.add_all([b"k%d" % i for i in range(1000)])
    h.count()
    snap = client.metrics.snapshot()
    assert snap["counters"]["executor.ops_total"] >= 2
    assert snap["counters"]["executor.keys_total"] >= 1000
    assert snap["counters"].get("executor.ops.hll_add", 0) >= 1
    assert snap["histograms"]["executor.batch_keys"]["count"] >= 1
    assert snap["gauges"]["executor.queue_depth"] == 0  # drained


def test_executor_error_metric(client):
    bf = client.get_bloom_filter("obs:bloom")
    with pytest.raises(Exception):
        bf.add(b"x")  # not initialized -> backend error
    assert client.metrics.counter("executor.errors_total") >= 1


def test_nodes_group_ping(client):
    ng = client.get_nodes_group()
    nodes = ng.nodes()
    assert any(n.kind == "device" for n in nodes)
    assert ng.ping_all()


def test_nodes_group_with_redis_tier():
    from redisson_tpu.config import Config
    from redisson_tpu.interop.fake_server import EmbeddedRedis

    with EmbeddedRedis() as er:
        cfg = Config()
        cfg.use_local()
        cfg.use_redis().address = f"redis://127.0.0.1:{er.port}"
        c = RedissonTPU.create(cfg)
        try:
            ng = c.get_nodes_group()
            kinds = {n.kind for n in ng.nodes()}
            assert kinds == {"device", "redis"}
            assert ng.ping_all()
        finally:
            c.shutdown()


def test_connection_listener_fanout(client):
    ng = client.get_nodes_group()
    events = []
    ng.add_connection_listener(lambda e, ident: events.append((e, ident)))
    ng.fire("connect", "node-1")
    ng.fire("disconnect", "node-1")
    assert events == [("connect", "node-1"), ("disconnect", "node-1")]
