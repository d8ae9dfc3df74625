"""Named host stages (trace/stages.py) and the always-on counters at the
same boundaries: the wire loop's busy time, the executor's staging time
and per-op queue waits, the completer's D2H and resolve time."""

import pathlib
import queue
import re

import pytest
from jax.profiler import TraceAnnotation

from redisson_tpu.client import RedissonTPU
from redisson_tpu.config import Config
from redisson_tpu.executor import CommandExecutor
from redisson_tpu.interop.resp_client import SyncRespClient
from redisson_tpu.observability import ExecutorMetrics, MetricsRegistry
from redisson_tpu.trace import STAGES, stage
from redisson_tpu.trace.hist import bucket_index, bucket_upper_ticks

PKG = pathlib.Path(__file__).resolve().parent.parent / "redisson_tpu"


def test_stage_is_a_shared_noop_without_a_profiler():
    assert not TraceAnnotation.is_enabled()
    off = stage("wire.read", bytes=12)
    assert off is stage("completer.d2h")
    with off:
        with stage("backend.launch", keys=3):
            pass


def test_every_stage_the_program_opens_is_in_the_table():
    used = set()
    for path in PKG.rglob("*.py"):
        used |= set(re.findall(r'\bstage\("([^"]+)"', path.read_text()))
    assert used == set(STAGES)


def _served_client():
    cfg = Config()
    cfg.use_wire()
    return RedissonTPU.create(cfg)


def test_stage_counters_advance_on_the_served_path():
    c = _served_client()
    try:
        cli = SyncRespClient("127.0.0.1", c.wire.port, retry_attempts=1)
        cli.connect()
        assert cli.execute("PFADD", "h", "a", "b") == 1
        assert cli.execute("SETBIT", "bits", 7, 1) == 0
        assert cli.execute("PFCOUNT", "h") == 2
        assert cli.execute("BITCOUNT", "bits") == 1
        cli.close()
        wire = c.wire.snapshot()
        ex = c._executor.pipeline_stats()
        backend = c._routing.sketch.counters
    finally:
        c.shutdown()
    assert wire["loop_busy_ns"] > 0
    assert ex["runs_staged"] >= 4 and ex["stage_ns"] > 0
    waits = {side: sum(n for k, n in ex.items()
                       if k.startswith(f"{side}_wait_le_us."))
             for side in ("read", "write")}
    assert waits["write"] >= 2 and waits["read"] >= 2
    assert backend["runs_materialized"] >= 4
    assert backend["d2h_wait_ns"] > 0 and backend["resolve_ns"] > 0


class HoldBackend:
    """run() hands each run's ops to the test, which resolves them."""

    batch_dims = {}

    def __init__(self):
        self.runs = queue.Queue()

    def run(self, kind, target, ops):
        self.runs.put(ops)


@pytest.mark.parametrize("kind,side", [("set", "write"), ("get", "read")])
def test_queue_wait_histogram_records_a_gated_ops_wait(kind, side):
    now = [10.0]
    metrics = ExecutorMetrics(MetricsRegistry())
    backend = HoldBackend()
    ex = CommandExecutor(backend, metrics=metrics, clock=lambda: now[0])
    try:
        first = ex.execute_async("k", kind, 1)
        held = backend.runs.get(timeout=5)
        # Same target: gated until the run in flight completes.
        second = ex.execute_async("k", kind, 2)
        now[0] = 10.25
        held[0].future.set_result(None)
        backend.runs.get(timeout=5)[0].future.set_result(None)
        first.result(timeout=5)
        second.result(timeout=5)
        stats = ex.pipeline_stats()
    finally:
        ex.shutdown()
    other = "read" if side == "write" else "write"
    assert metrics.queue_wait[side].count == 2
    assert metrics.queue_wait[other].count == 0
    gated = bucket_upper_ticks(bucket_index(250_000))
    assert stats[f"{side}_wait_le_us.0"] == 1
    assert stats[f"{side}_wait_le_us.{gated}"] == 1
    assert not any(k.startswith(f"{other}_wait_le_us.") for k in stats)
    assert stats["runs_staged"] == 2
