"""A later PR adds a configuration, a traffic mix, a per-layer metric, a
kernel cost, and a state type with a command of its own by adding files
and BENCHMARK.json entries alone: no file of the harness is edited."""

import json
import os
import shutil
import subprocess
import sys

from perfbench import spec, trace_reduce

ROOT = spec.ROOT


HARNESS = ("run.py", "spec.py", "traffic.py", "check.py", "system.py",
           "trace_reduce.py", "loadgen.py")


def _copy(tmp_path):
    """The benchmark's paths copied under `tmp_path`, beside the program;
    returns perfbench/ there and the bytes of its harness files."""
    for p in spec.benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "redisson_tpu"), tmp_path / "redisson_tpu")
    pb = tmp_path / "perfbench"
    return pb, {p: (pb / p).read_bytes() for p in HARNESS}


def _add_cell(tmp_path, config: str, mix: str) -> str:
    """BENCHMARK.json in the copy with the cell of `config` under `mix`."""
    bench = spec.benchmark()
    bench["configs"].append({"name": config, "source": "test",
                             "file": f"perfbench/configs/{config}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{config}.{mix}", "config": config,
                               "traffic": mix, "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{config}.{mix}"


def test_a_dummy_cell_from_files_alone(tmp_path):
    pb, before = _copy(tmp_path)
    (pb / "configs" / "dummy_small.json").write_text(json.dumps({
        "name": "dummy_small", "source": "test", "reduced": [],
        "program": {"tpu": {"ingest": "auto"}, "wire": {}},
        "space": {"pages": 8, "users": 1024},
        "state": [{"type": "hll", "key": "h:{page}",
                   "for": {"page": [0, "pages"]},
                   "fill": {"elements": 4, "of": "users"}}]}))
    (pb / "traffic" / "dummy_mix.json").write_text(json.dumps({
        "warmup_s": 1,
        "writers": {"connections": 2, "pipeline": 1,
                    "command": ["PFADD", "h:{conn+connections*page}",
                                {"elements": 3, "of": "users"}],
                    "vars": {"page": {"zipf": 0.99,
                                      "n": "pages/connections"}}},
        "readers": {"connections": 1, "rate": 20,
                    "commands": [["PFCOUNT", "h:{page}", "h:0"]],
                    "vars": {"page": {"uniform": [0, "pages"]}}},
        "limits": {"answer_gap": 1e-5}}))
    (pb / "layers" / "dummy.writes_per_s.py").write_text(
        "def read(ctx):\n    return ctx['writes'] / ctx['seconds']\n")
    (pb / "kernels" / "dummy_kernel.py").write_text(
        "def match(text):\n    return text.startswith('%dummy')\n\n"
        "def cost(text):\n    return 0.0, 1024.0\n")
    _add_cell(tmp_path, "dummy_small", "dummy_mix")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "dummy.writes_per_s", "unit": "ops/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "write_ops_per_s",
                               "workloads": ["dummy_small.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "dummy_small.dummy_mix", "--seed", "4000000000", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr.decode()[-3000:]
    res = json.loads(out.stdout.decode().strip().splitlines()[-1])
    assert res["correct"] and res["metrics"]["write_ops_per_s"]["value"] > 0

    cell = spec.load_cell("dummy_small.dummy_mix", root=str(tmp_path))
    assert [m["name"] for m in cell.per_layer][-1] == "dummy.writes_per_s"
    reader = spec.load_module("layers", "dummy.writes_per_s",
                              root=str(tmp_path))
    assert reader.read({"writes": 50, "seconds": 2.0}) == 25.0
    kernels = trace_reduce.kernel_modules(str(tmp_path))
    red = trace_reduce.reduce({
        "window_ns": [0, 1000], "host": [],
        "devices": {"0": {"modules": [["jit_x", 0, 500]],
                          "ops": [["%dummy.1 = u8[1024]{0} custom-call()",
                                   100, 200]]}}}, kernels)
    assert red["kernels"]["dummy_kernel"] == {
        "seconds": 2e-7, "launches": 1, "ops": 0.0, "bytes": 1024.0}
    assert before == {p: (pb / p).read_bytes() for p in before}


#: A state type of the test's own: flag sets whose fill sets `fill.set`
#: offsets drawn from the seed by SETBIT through the facade, read by
#: GETBIT, a command no module of the harness models; the writes are the
#: `bits` type's SETBIT.
FLAGS = '''
import numpy as np

from perfbench import traffic
from perfbench.ref import bits as rbits

READ_KINDS = frozenset({"bitset_get"})


def _offsets(entry, key_index, seed, space):
    nbits = traffic.value(entry["bits"], space)
    ids = traffic.fill_elements(seed, np.asarray([key_index]),
                                int(entry["fill"]["set"]), nbits)[0]
    return nbits, [int(x) for x in ids]


def fill(system, keys, seed):
    for k, e, i in keys:
        system.client.get_bit_set(k).set_bits(
            _offsets(e, i, seed, system.space)[1])


def base(entry, key_index, seed, space):
    nbits, offsets = _offsets(entry, key_index, seed, space)
    return rbits.set_bits(rbits.make(nbits), offsets)


def readback(key):
    return ["BITCOUNT", key]


class GetBit:
    rounds = False

    def keys_read(self, args):
        return [args[1]]

    def answer(self, args, states):
        return float(rbits.get_bits(states[0], [int(args[2])])[0])


WRITES = {}
READS = {"GETBIT": GetBit()}
'''


def test_a_state_type_and_its_command_from_files_alone(tmp_path):
    pb, before = _copy(tmp_path)
    (pb / "state" / "flags.py").write_text(FLAGS)
    (pb / "configs" / "dummy_flags.json").write_text(json.dumps({
        "name": "dummy_flags", "source": "test", "reduced": [],
        "program": {"tpu": {"ingest": "auto"}, "wire": {}},
        "space": {"pages": 4, "flags": 64},
        "state": [{"type": "flags", "key": "f:{page}", "bits": "flags",
                   "for": {"page": [0, "pages"]}, "fill": {"set": 8}}]}))
    (pb / "traffic" / "dummy_getbit.json").write_text(json.dumps({
        "warmup_s": 1,
        "writers": {"connections": 2, "pipeline": 1,
                    "command": ["SETBIT", "f:{conn+connections*page}",
                                "{bit}", "1"],
                    "vars": {"page": {"uniform": [0, "pages/connections"]},
                             "bit": {"uniform": [0, "flags"]}}},
        "readers": {"connections": 1, "rate": 20,
                    "commands": [["GETBIT", "f:{page}", "{bit}"]],
                    "vars": {"page": {"uniform": [0, "pages"]},
                             "bit": {"uniform": [0, "flags"]}}},
        "limits": {"answer_gap": 0}}))
    cell = _add_cell(tmp_path, "dummy_flags", "dummy_getbit")

    out = subprocess.run(
        [sys.executable, "perfbench/control.py", "--workload", cell,
         "--seeds", "4000000001", "--seconds", "1"], cwd=tmp_path,
        capture_output=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr.decode()[-3000:]
    res = json.loads(out.stdout.decode().strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["metrics"]["write_ops_per_s"]["value"] > 0
    worst = [ln for ln in out.stderr.decode().splitlines()
             if ln.startswith("# worst read")]
    assert "'GETBIT'" in worst[-1], worst
    ctl = res["control_checks"]
    assert any(c["value"] > c["limit"] for c in ctl.values()), ctl
    assert before == {p: (pb / p).read_bytes() for p in before}
