"""The program's stage spans in a profiler capture, idle gaps named by
span (perfbench/host_spans.py), and the readers of the stage metrics."""

import gzip
import json
import os

import pytest

from perfbench import host_spans, spec, trace_reduce

ROOT = spec.ROOT
FIXTURE = os.path.join(ROOT, "perfbench", "fixtures", "v5e_trace.json.gz")


def test_a_cpu_capture_holds_every_served_stage(tmp_path):
    import jax

    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.interop.resp_client import SyncRespClient
    from redisson_tpu.trace import STAGES

    cfg = Config()
    cfg.use_wire()
    c = RedissonTPU.create(cfg)
    try:
        cli = SyncRespClient("127.0.0.1", c.wire.port, retry_attempts=1)
        cli.connect()
        cli.execute("PFADD", "warm", "x")  # compile outside the capture
        cli.execute("BITCOUNT", "warm-bits")
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert cli.execute("PFADD", "h", "a", "b") == 1
            assert cli.execute("SETBIT", "bits", 3, 1) == 0
            assert cli.execute("PFCOUNT", "h") == 2
            assert cli.execute("BITCOUNT", "bits") == 1
        finally:
            jax.profiler.stop_trace()
        cli.close()
    finally:
        c.shutdown()
    host, stats = host_spans.host_events(
        trace_reduce.find_xplane(str(tmp_path)))
    names = {name for _, name, _, _ in host}
    assert {host_spans.PREFIX + s for s in STAGES} <= names
    assert stats["flushed_ops"] == 4  # four commands, one per wire window
    assert stats["spans"][host_spans.PREFIX + "wire.flush"] == 4


def _synthetic():
    """Device busy [10,20) [40,50) [70,80) of a [0,100) window; the four
    gaps' midpoints are 5, 30, 60 and 90."""
    return {
        "window_ns": [0, 100],
        "devices": {"0": {"ops": [["%a", 10, 10], ["%b", 40, 10],
                                  ["%c", 70, 10]], "modules": []}},
        "host": [
            # mid 5: a frame inside a span -> the span
            [1, "rtpu/wire.flush", 0, 12],
            [1, "$server.py:1 _flush", 2, 6],
            # mid 30: nested spans, a longer one on another thread -> the
            # innermost
            [2, "rtpu/executor.dispatch", 25, 13],
            [2, "rtpu/backend.run", 28, 5],
            [3, "rtpu/completer.d2h", 20, 25],
            # mid 60: a frame and no span -> the frame
            [1, "$x.py:1 work", 55, 10],
            # mid 90: only a wait -> host waiting
            [2, "$threading.py:323 wait", 85, 10],
        ],
    }


def test_gaps_are_named_by_the_innermost_span_then_the_frame():
    red = host_spans.reduce(_synthetic(), {})
    assert red["idle_by_cause"] == pytest.approx({
        "rtpu/wire.flush": 10e-9, "rtpu/backend.run": 20e-9,
        "x.py:1 work": 20e-9, "host waiting": 20e-9})
    assert red["idle_by_span"] == pytest.approx({
        "rtpu/wire.flush": 10e-9, "rtpu/backend.run": 20e-9})
    assert dict(red["breakdown"]["idle_gaps"]) == red["idle_by_cause"]
    # trace_reduce alone names the first gap by the shorter frame
    plain = trace_reduce.reduce(_synthetic(), {})
    assert "server.py:1 _flush" in plain["idle_by_cause"]


def test_the_recorded_fixture_reduces_exactly_as_before():
    with gzip.open(FIXTURE, "rt") as f:
        recorded = json.load(f)
    kernels = trace_reduce.kernel_modules(ROOT)
    before = trace_reduce.reduce(recorded, kernels)
    after = host_spans.reduce(recorded, kernels)
    assert after.pop("idle_by_span") == {}
    assert after == before


@pytest.mark.parametrize("metric,ctx,value", [
    ("wire.loop_busy_pct",
     {"delta": {"wire": {"loop_busy_ns": 5_000_000_000}}, "seconds": 20.0},
     25.0),
    ("executor.write_wait_p95_ms",
     {"delta": {"executor": {"write_wait_le_us.100": 90,
                             "write_wait_le_us.2047": 5,
                             "write_wait_le_us.40959": 5,
                             "read_wait_le_us.9": 1000}}},
     2.047),
    ("backend.stage_ms_per_run",
     {"delta": {"executor": {"stage_ns": 6_000_000, "runs_staged": 4}}},
     1.5),
    ("completer.d2h_wait_ms_per_run",
     {"delta": {"backend": {"d2h_wait_ns": 50_000_000,
                            "runs_materialized": 10}}},
     5.0),
    ("device.idle_unattributed_pct",
     {"trace": {"window_s": 2.0,
                "idle_by_cause": {"rtpu/wire.read": 1.0,
                                  "host waiting": 0.5}}},
     25.0),
    ("wire.read_us_per_command",
     {"delta": {"wire": {"read_busy_ns": 3_000_000, "commands_total": 20_000,
                         "loop_busy_ns": 9_000_000}}, "seconds": 20.0},
     0.15),
    # a cluster's counters are summed over its servers: the mean of them all
    ("wire.read_us_per_command",
     {"delta": {"wire": {"read_busy_ns": 400_000_000 + 200_000_000,
                         "commands_total": 3_000 + 1_000}}},
     150.0),
    # no command read in the window: nothing to read
    ("wire.read_us_per_command",
     {"delta": {"wire": {"read_busy_ns": 5_000, "commands_total": 0}},
      "seconds": 20.0},
     None),
])
def test_each_stage_reader_on_a_known_ctx(metric, ctx, value):
    got = spec.load_module("layers", metric).read(ctx)
    assert got == (None if value is None else pytest.approx(value))


@pytest.mark.parametrize("metric", [
    "wire.loop_busy_pct", "executor.write_wait_p95_ms",
    "backend.stage_ms_per_run", "completer.d2h_wait_ms_per_run",
    "wire.read_us_per_command"])
def test_a_program_without_the_counters_reads_nothing(metric):
    ctx = {"delta": {"wire": {"ops_flushed": 9},
                     "executor": {"runs_completed": 3},
                     "backend": {"link_bytes": 0}},
           "seconds": 20.0}
    assert spec.load_module("layers", metric).read(ctx) is None
