"""Tiny sizes for running the benchmark's cells on the CPU in tests."""

from perfbench import spec

TINY = {"config": {"space": {"pages": 16, "users": 4096, "closed_days": 8}},
        "traffic": {"warmup_s": 1, "writers": {"connections": 4},
                    "readers": {"connections": 1, "rate": 40}}}


def tiny_cell(name: str):
    return spec.load_cell(name, overrides=TINY)


def cell_names():
    return [w["name"] for w in spec.benchmark()["workloads"]]


def cluster_files(root, plane: str) -> str:
    """A 4-shard cluster configuration and its traffic mix as a later PR
    would add them, in the copy of the benchmark at `root`; returns the
    cell's name. On the stacks plane a read's keys share a hashtag, as a
    Redis Cluster client's multi-key commands must; on the mesh plane the
    7-day-style unions span shards."""
    import json
    import os

    tag = "{{page}}" if plane == "stacks" else "{page}"
    name = f"dummy_cluster_{plane}"
    pb = os.path.join(root, "perfbench")
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(pb, d), exist_ok=True)
    with open(os.path.join(pb, "configs", name + ".json"), "w") as f:
        json.dump({
            "name": name, "source": "test", "reduced": [],
            "program": {"tpu": {"ingest": "auto"}, "wire": {},
                        "cluster": {"num_shards": 4, "data_plane": plane}},
            "space": {"pages": 32, "days": 3, "users": 4096},
            "state": [{"type": "hll", "key": "d{day}:" + tag,
                       "for": {"day": [0, "days"], "page": [0, "pages"]},
                       "fill": {"elements": 4, "of": "users"}},
                      {"type": "hll", "key": "today:" + tag,
                       "for": {"page": [0, "pages"]},
                       "fill": {"elements": 4, "of": "users"}}]}, f)
    mix = f"dummy_cluster_{plane}_mix"
    with open(os.path.join(pb, "traffic", mix + ".json"), "w") as f:
        json.dump({
            "warmup_s": 1,
            "writers": {"connections": 8, "pipeline": 1,
                        "command": ["PFADD",
                                    "today:" + tag.replace(
                                        "page", "conn+connections*page"),
                                    {"elements": 3, "of": "users"}],
                        "vars": {"page": {"zipf": 0.99,
                                          "n": "pages/connections"}}},
            "readers": {"connections": 2, "rate": 40,
                        "commands": [["PFCOUNT"] + [f"d{d}:" + tag
                                                    for d in range(3)]],
                        "vars": {"page": {"zipf": 0.99, "n": "pages"}}},
            "limits": {"answer_gap": 1e-5}}, f)
    bench = spec.benchmark()
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"perfbench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.{mix}", "config": name,
                               "traffic": mix, "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return f"{name}.{mix}"
