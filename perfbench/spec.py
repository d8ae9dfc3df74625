"""Find a cell's parts by name: `BENCHMARK.json` names the cell, its
configuration (`perfbench/configs/<config>.json`) and its traffic mix
(`perfbench/traffic/<traffic>.json`); per-layer metric readers live in
`perfbench/layers/<metric>.py`, kernel cost functions in
`perfbench/kernels/<kernel>.py` and state types in
`perfbench/state/<type>.py`. Nothing here knows a cell by name.

A state type is what a configuration's `state` entry names in `type`, and
its module holds everything the harness knows of that structure:

- `fill(system, keys, seed)`: the program side of the fill, through the
  System's `client`, `dispatch` and `space`; `keys` are the
  configuration's `(key, entry, key_index)` of this type, in file order;
- `base(entry, key_index, seed, space)`: the key's state after the fill,
  from the plain references (perfbench/ref);
- `READ_KINDS`: the program's op kinds that answer this type's reads;
- `readback(key)`: the command that reads a key back after the window;
- `WRITES`: write command name -> model with `payload(args)` (what the
  write adds), `apply(state, payload)` and `wrong(history, replies,
  control)`, the (wrong, checked) replies of one key's history that no
  serial order gives, or the control's (`run.CONTROL`) in their place;
- `READS`: read command name -> model with `keys_read(args)`,
  `answer(args, states)` (a number, or an array for an array reply) and
  `rounds` (the program rounds the answer to an integer); a read that
  makes a key of the read's own (BITOP's destination) also has
  `makes(args)` and `make(args, states)`.

It imports neither jax nor redisson_tpu at module level: the load
generator never imports jax, and `fill` reaches the program through the
System."""

from __future__ import annotations

import copy
import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def space(self) -> Dict[str, int]:
        return self.config["space"]


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _merge(base: Dict[str, Any], over: Optional[Dict[str, Any]]):
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT, bench: Dict[str, Any] = None,
              overrides: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell `name` of BENCHMARK.json with its configuration and traffic
    loaded; `overrides` ({"config": {...}, "traffic": {...}}) is merged in
    (tests shrink sizes this way; the benchmark's runs pass none)."""
    bench = bench or benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, confs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "perfbench", "traffic",
                                      w["traffic"] + ".json"))
    overrides = overrides or {}
    return Cell(name=name, chips=int(w["chips"]),
                config=_merge(config, overrides.get("config")),
                traffic=_merge(traffic, overrides.get("traffic")),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def state_type(name: str, root: str = ROOT):
    """perfbench/state/<name>.py, the module of the state type `name`."""
    path = os.path.join(root, "perfbench", "state", name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown state type {name!r}: there is no {path}")
    return load_module("state", name, root)


def state_types(root: str = ROOT) -> Dict[str, Any]:
    """Every perfbench/state/<type>.py, by type."""
    names = sorted(f[:-3] for f in os.listdir(
        os.path.join(root, "perfbench", "state")) if f.endswith(".py"))
    return {n: load_module("state", n, root) for n in names}


def command_models(types: Dict[str, Any]):
    """(writes, reads): every state type's WRITES and READS merged, by
    upper-case command name. A command that two types model is an error,
    and so is DEL, which the harness answers (a read deletes the keys it
    made)."""
    writes: Dict[str, Any] = {}
    reads: Dict[str, Any] = {}
    owner = {"DEL": "the harness"}
    for t, mod in types.items():
        for table, out in ((mod.WRITES, writes), (mod.READS, reads)):
            for name, model in table.items():
                name = name.upper()
                if name in owner:
                    raise ValueError(f"{name} is modelled by both "
                                     f"{owner[name]} and state type {t!r}")
                owner[name] = f"state type {t!r}"
                out[name] = model
    return writes, reads


def load_module(kind: str, name: str, root: str = ROOT):
    """perfbench/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(root, "perfbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
