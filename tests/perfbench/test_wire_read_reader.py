"""The reader of wire.read_us_per_command: the wire loop's socket-read
parse and dispatch time a command, from WireServer.snapshot() counters."""

import pytest

from perfbench import spec

METRIC = "wire.read_us_per_command"


@pytest.mark.parametrize("ctx,value", [
    ({"delta": {"wire": {"read_busy_ns": 3_000_000, "commands_total": 20_000,
                         "loop_busy_ns": 9_000_000}}, "seconds": 20.0},
     0.15),
    # a cluster's counters are summed over its servers: the mean of them all
    ({"delta": {"wire": {"read_busy_ns": 400_000_000 + 200_000_000,
                         "commands_total": 3_000 + 1_000}}},
     150.0),
])
def test_read_us_per_command_on_a_known_ctx(ctx, value):
    assert spec.load_module("layers", METRIC).read(ctx) == pytest.approx(value)


@pytest.mark.parametrize("wire", [
    {"ops_flushed": 9},
    {"read_busy_ns": 5_000, "commands_total": 0},
], ids=["no_counters", "no_commands"])
def test_a_program_without_the_counters_reads_nothing(wire):
    ctx = {"delta": {"wire": wire}, "seconds": 20.0}
    assert spec.load_module("layers", METRIC).read(ctx) is None
