"""The timed path broken underneath: `correct` has to come out false.

Each fault is planted in the program's wire command table, where the
server builds the engine ops of a command and renders its reply, so the
rest of the run (wire, serve, executor, kernels, reference) is the real
one."""

import numpy as np
import pytest

from perfbench import run
from redisson_tpu.wire import commands, proto
from tests.perfbench.helpers import tiny_cell

HLL = "hll_page_uniques.sharded_ingest"
BITS = "bitmap_daily_active.sharded_logins"


def _unchanged(build):
    """A write that returns its reply but leaves the state unchanged."""
    def b(client, args):
        call = build(client, args)
        key = call.ops[0][0]
        ops = [(key, "exists", None, 1)]
        return commands.EngineCall(ops, lambda rs, p: proto.integer(0), key)
    return b


def _half_batch(build):
    """PFADD of half the ids; for SETBIT, every other command dropped."""
    state = {"n": 0}

    def b(client, args):
        if args[0].upper() == b"PFADD":
            n = 2 + (len(args) - 2 + 1) // 2
            return build(client, args[:n])
        state["n"] += 1
        call = build(client, args)
        if state["n"] % 2:
            return call
        key = call.ops[0][0]
        return commands.EngineCall([(key, "exists", None, 1)],
                                   lambda rs, p: proto.integer(0), key)
    return b


def _altered(build):
    """A read's answer altered where it is rendered: doubled."""
    def b(client, args):
        call = build(client, args)
        render = call.render

        def wrong(rs, p):
            v = int(render(rs, p)[1:-2])
            return proto.integer(2 * v + 1)
        return commands.EngineCall(call.ops, wrong, call.key)
    return b


def _refused(build):
    """A command answered with an error reply, as a shed would be."""
    state = {"n": 0}

    def b(client, args):
        state["n"] += 1
        call = build(client, args)
        if state["n"] % 3:
            return call
        return commands.EngineCall(
            call.ops, lambda rs, p: proto.err("try again", "BUSY"), call.key)
    return b


CASES = [
    (HLL, b"PFADD", _unchanged), (HLL, b"PFADD", _half_batch),
    (HLL, b"PFCOUNT", _altered), (HLL, b"PFADD", _refused),
    (BITS, b"SETBIT", _unchanged), (BITS, b"SETBIT", _half_batch),
    (BITS, b"BITCOUNT", _altered), (BITS, b"BITCOUNT", _refused),
]


@pytest.mark.parametrize("cell,command,fault", CASES,
                         ids=[f"{c.split('.')[1]}-{m.decode()}-{f.__name__}"
                              for c, m, f in CASES])
def test_fault_makes_correct_false(monkeypatch, cell, command, fault):
    build = commands.ENGINE_COMMANDS[command]
    monkeypatch.setitem(commands.ENGINE_COMMANDS, command, fault(build))
    res = run.run_cell(tiny_cell(cell), 2**31 + 3, 1.5, False)
    assert not res["correct"], res["checks"]
    assert np.isfinite(list(res["metrics"].values())[0]["value"])


def test_wrong_slot_map_makes_correct_false(monkeypatch, tmp_path):
    """A cluster client given a slot map in which two shards' ranges are
    swapped sends their keys to the wrong node: those commands get -MOVED
    replies, which the load generator does not follow."""
    from perfbench import spec
    from redisson_tpu.cluster.manager import ClusterManager
    from tests.perfbench.helpers import cluster_files

    real = ClusterManager.cluster_slots

    def swapped(self):
        ranges = real(self)
        (s0, e0, a, r0), (s1, e1, b, r1) = ranges[:2]
        return [(s0, e0, b, r0), (s1, e1, a, r1)] + ranges[2:]

    monkeypatch.setattr(ClusterManager, "cluster_slots", swapped)
    cell = spec.load_cell(cluster_files(tmp_path, "stacks"),
                          root=str(tmp_path))
    res = run.run_cell(cell, 2**31 + 5, 1.5, False)
    assert not res["correct"], res["checks"]
    assert res["checks"]["failed_replies"]["value"] > 0
    assert res["failed"] > 0
