"""The system under test, built from a configuration file: with the state
types' `fill` functions (perfbench/state/), which it calls, the only code
of the benchmark that imports redisson_tpu. It starts the program with the
configuration's settings, fills its state in bulk, and reads the program's
counters and spans; the load itself comes over the wire from the load
generator children.

A configuration's `program` maps sections of the program's `Config` to
their fields: for each section the System calls `Config.use_<section>()`
and sets the fields (`{"tpu": {...}, "wire": {}, "cluster": {"num_shards":
4, "data_plane": "mesh"}}`). The TPU section is always on. A section or a
field that `Config` lacks is an error, and so is a `dir`: a path of the
run's own is not supported yet.

In a cluster (a `cluster` section, served with `wire`) each shard has a
wire server of its own on its own port and loop thread. `counters()` keeps
the shape of one server's: the wire counts are summed over the servers,
except `loop_busy_ns`, which `window_delta` makes the busiest server's, so
it stays one thread's share; the executor and backend counters are the
mesh plane's one shared engine's, or summed over the stacks plane's
engines. The read-back goes to the node that owns each key, as the slot
map of `CLUSTER SLOTS` says."""

from __future__ import annotations

import dataclasses
import inspect
import socket
from typing import Any, Dict, Iterable, List

from perfbench import resp, slots, spec, traffic

#: op kinds that answer a read (per-layer metrics of reads use these): the
#: union of every state type's READ_KINDS
READ_KINDS = frozenset().union(*(m.READ_KINDS for m in
                                 spec.state_types().values()))

_BUSY = "loop_busy_ns"


def state_keys(config: Dict[str, Any]):
    """[(key, entry, key_index)] of every key the configuration holds, in
    file order; key_index seeds that key's fill."""
    out = []
    for entry in config["state"]:
        for key in traffic.expand_keys(entry, config["space"]):
            out.append((key, entry, len(out)))
    return out


def program_config(program: Dict[str, Dict[str, Any]]):
    """The program's Config from a configuration's `program` section."""
    from redisson_tpu.config import Config

    cfg = Config()
    cfg.use_tpu()
    for section, fields in program.items():
        use = getattr(cfg, f"use_{section}", None)
        if use is None:
            raise ValueError(f"the program's Config has no section "
                             f"{section!r}")
        if "dir" in fields:
            raise ValueError(f"{section}.dir: a per-run path is not "
                             "supported")
        # fields that use_<section> takes go through it, and its checks
        takes = set(inspect.signature(use).parameters)
        sub = use(**{k: v for k, v in fields.items() if k in takes})
        known = {f.name for f in dataclasses.fields(sub)}
        for k, v in fields.items():
            if k not in known:
                raise ValueError(f"the program's Config.{section} has no "
                                 f"field {k!r}")
            setattr(sub, k, v)
    return cfg


def _numbers(d: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def _summed(parts: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """One member's counters as they are; several members' numbers
    summed key by key."""
    parts = list(parts)
    if len(parts) == 1:
        return dict(parts[0])
    out: Dict[str, Any] = {}
    for d in parts:
        for k, v in _numbers(d).items():
            out[k] = out.get(k, 0) + v
    return out


def window_delta(c0, c1) -> Dict[str, Dict[str, Any]]:
    """c1 - c0 of every numeric counter. Where `wire` holds one
    `loop_busy_ns.<server>` a server, its `loop_busy_ns` is the busiest
    server's delta."""
    out = {}
    for part, d1 in c1.items():
        d0 = c0.get(part, {})
        out[part] = {k: v - d0.get(k, 0) for k, v in _numbers(d1).items()}
    busy = [v for k, v in out.get("wire", {}).items()
            if k.startswith(_BUSY + ".")]
    if busy:
        out["wire"][_BUSY] = max(busy)
    return out


class System:
    def __init__(self, config: Dict[str, Any], traced: bool):
        from redisson_tpu.client import RedissonTPU

        self.space = config["space"]
        self._keys = state_keys(config)
        self._types = {e["type"]: spec.state_type(e["type"])
                       for e in config["state"]}
        cfg = program_config(config["program"])
        self.spans: List[tuple] = []
        if traced:
            # Every op gets a span in the traced run (default: 1 in 128).
            cfg.use_trace().sample_every = 1
        self.client = RedissonTPU.create(cfg)
        manager = self.client.cluster
        self.cluster = manager is not None
        # where a state type's fill sends an engine op by key
        self.dispatch = (self.client._dispatch if self.cluster
                         else self.client._executor)
        # the engine clients behind the facade, and the wire servers
        if not self.cluster:
            self.engines = {"": self.client}
            self.servers = {"": self.client.wire}
        elif manager.mesh_client is not None:
            self.engines = {"mesh": manager.mesh_client}
            self.servers = dict(self.client.wire.servers)
        else:
            self.engines = {f"shard{sid}": s.client
                            for sid, s in sorted(manager.shards.items())}
            self.servers = dict(self.client.wire.servers)
        if traced:
            for e in self.engines.values():
                e.trace.tracer.add_sink(self._on_span)
        self.backends = {n: e._routing.sketch
                         for n, e in self.engines.items()}
        self.host = "127.0.0.1"
        self.port = self.servers[min(self.servers)].port
        self._socks: Dict[tuple, tuple] = {}  # addr -> (socket, parser)
        self._slot_map = None

    def _on_span(self, span) -> None:
        from redisson_tpu.trace.spans import stage_breakdown

        if span.span_type == "op":
            st = stage_breakdown(span)
            self.spans.append((span.kind, span.t1 or 0.0,
                               st.get("queue", 0.0)))

    # -- fill ---------------------------------------------------------------

    def fill(self, seed: int) -> None:
        """Every key of the configuration, filled by its state type's
        `fill` (perfbench/state/<type>.py): one type after another, in the
        order the configuration first names them, each with its keys in
        file order."""
        by_type: Dict[str, list] = {}
        for k, e, i in self._keys:
            by_type.setdefault(e["type"], []).append((k, e, i))
        for t, keys in by_type.items():
            self._types[t].fill(self, keys, seed)

    # -- counters -----------------------------------------------------------

    def counters(self) -> Dict[str, Dict[str, Any]]:
        snaps = {n: s.snapshot() for n, s in self.servers.items()}
        wire = _summed(snaps.values())
        if self.cluster:
            busy = {f"{_BUSY}.{n}": s[_BUSY] for n, s in snaps.items()}
            wire.update(busy)
            wire[_BUSY] = max(busy.values())
        return {"wire": wire,
                "executor": _summed(e._executor.pipeline_stats()
                                    for e in self.engines.values()),
                "backend": _summed(b.counters
                                   for b in self.backends.values())}

    def rule_inputs(self) -> Dict[str, Any]:
        """The measured inputs of the program's ingest link rule
        (`backend_tpu.RuleInputs`); in a cluster, keyed by engine
        (`shard<i>`, or `mesh` for the one shared engine)."""
        from redisson_tpu.backend_tpu import RuleInputs

        tables = {n: RuleInputs(b.store.device).table()
                  for n, b in self.backends.items()}
        return tables if self.cluster else tables[""]

    # -- read-back over the wire ------------------------------------------

    def _conn(self, addr, timeout: float):
        if addr not in self._socks:
            self._socks[addr] = (socket.create_connection(addr,
                                                          timeout=timeout),
                                 resp.Parser())
        return self._socks[addr]

    def command(self, args, timeout: float = 120.0):
        """One command over a blocking loopback connection of the
        benchmark's own, to the node that owns its key in a cluster;
        returns the parsed reply."""
        addr = (self.host, self.port)
        if self.cluster:
            if self._slot_map is None:
                self._slot_map = slots.SlotMap(resp.call(
                    *self._conn(addr, timeout), ["CLUSTER", "SLOTS"]))
            m = self._slot_map
            addr = m.nodes[m.node_of(slots.command_key(args))]
        return resp.call(*self._conn(addr, timeout), args)

    def close(self) -> None:
        for sock, _ in self._socks.values():
            sock.close()
        self.client.shutdown()
