"""A sharded cluster from its configuration file alone: a later PR adds a
configuration whose `program` holds a `cluster` section, and a traffic
mix, as files and BENCHMARK.json entries; the harness starts the cluster
and drives it as a Redis Cluster client, with no harness file edited."""

import os
import shutil

import pytest

from perfbench import run, spec
from tests.perfbench.helpers import cluster_files

HARNESS = ("run.py", "spec.py", "traffic.py", "check.py", "system.py",
           "loadgen.py", "slots.py", "resp.py")


@pytest.mark.parametrize("plane", ["stacks", "mesh"])
def test_a_cluster_cell_from_files_alone(tmp_path, plane):
    for p in spec.benchmark()["paths"]:
        shutil.copytree(os.path.join(spec.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    pb = tmp_path / "perfbench"
    before = {p: (pb / p).read_bytes() for p in HARNESS}
    name = cluster_files(tmp_path, plane)
    cell = spec.load_cell(name, root=str(tmp_path))
    seen = {}
    res = run.run_cell(cell, 2**31 + 25, 1.5, False, control=True,
                       patch=lambda system: seen.update(system=system))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["control_checks"]["answer_gap"]["value"] > \
        res["control_checks"]["answer_gap"]["limit"]
    servers = seen["system"].servers
    assert len(servers) == 4
    # no command was sent to a node that does not own its key, and every
    # shard's server served writes
    assert sum(s.redirects_rendered for s in servers.values()) == 0
    assert all(s.engine_commands > 0 for s in servers.values())
    assert before == {p: (pb / p).read_bytes() for p in HARNESS}
