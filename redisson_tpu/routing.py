"""RoutingBackend — composes the device sketch backend with the structures
engine behind the single CommandExecutor waist.

The analogue of the reference's NodeSource routing inside
`CommandAsyncService.async()` (`command/CommandAsyncService.java:378`):
where the reference picks a Redis node per key slot, we pick the *tier* per
op kind — sketch kinds go to the TPU/pod backend, everything else to the
in-process structure engine. Keyspace-wide ops (delete/exists/flushall/keys)
fan out to both tiers and reduce, mirroring `readAllAsync` + SlotCallback
(`CommandAsyncService.java:128-164`).
"""

from __future__ import annotations

from typing import List, Optional

from redisson_tpu.executor import Op
from redisson_tpu.structures.engine import StructureBackend


class RoutingBackend:
    """kind-based router between the sketch tier and the structure tier."""

    def __init__(self, sketch_backend, structures: Optional[StructureBackend] = None):
        self.sketch = sketch_backend
        self.structures = structures or StructureBackend()
        self.GLOBAL_COALESCE = frozenset(getattr(sketch_backend, "GLOBAL_COALESCE", ()))
        self.COALESCE_GROUPS = dict(getattr(sketch_backend, "COALESCE_GROUPS", {}))
        self.BLOOM_STRICT_MOD = bool(getattr(sketch_backend, "BLOOM_STRICT_MOD", False))
        # Both tiers commit all observable state inside run() (the structure
        # engine resolves synchronously), so the router is dispatch-time-state
        # exactly when the sketch tier is — the executor may then release
        # per-target gates at staging time and pipeline the device work.
        self.DISPATCH_TIME_STATE = bool(
            getattr(sketch_backend, "DISPATCH_TIME_STATE", False))
        self.pubsub = self.structures.pubsub

    # sketch kinds = everything the sketch backend implements, minus the
    # keyspace-wide ops we intercept.
    _BOTH = {"delete", "exists", "flushall", "keys", "rename"}

    def _sketch_handles(self, kind: str) -> bool:
        # Backends that wrap a delegate (PodBackend) answer through
        # handles(); plain backends by _op_* probing.
        handles = getattr(self.sketch, "handles", None)
        if callable(handles):
            return handles(kind)
        return hasattr(self.sketch, "_op_" + kind)

    def run(self, kind: str, target: str, ops: List[Op]) -> None:
        if kind in self._BOTH:
            getattr(self, "_both_" + kind)(target, ops)
            return
        if self._sketch_handles(kind):
            self.sketch.run(kind, target, ops)
            return
        self.structures.run(kind, target, ops)

    # -- keyspace-wide fan-out ----------------------------------------------

    def _sketch_side(self, kind: str, target: str):
        """Run the sketch backend's own handler (it may hold state outside
        the store, e.g. the pod bank rows) and return its result."""
        probe = Op(target=target, kind=kind, payload=None)
        self.sketch.run(kind, target, [probe])
        # graftlint: allow-block(same-thread: run() above completes the probe future before returning)
        return probe.future.result()

    def _both_delete(self, target: str, ops: List[Op]) -> None:
        res = bool(self._sketch_side("delete", target)) | self.structures.delete(target)
        for op in ops:
            op.future.set_result(res)

    def _both_exists(self, target: str, ops: List[Op]) -> None:
        res = bool(self._sketch_side("exists", target)) or self.structures.exists(target)
        for op in ops:
            op.future.set_result(res)

    def _both_flushall(self, target: str, ops: List[Op]) -> None:
        self._sketch_side("flushall", "")
        self.structures.flushall()
        for op in ops:
            op.future.set_result(None)

    def _both_rename(self, target: str, ops: List[Op]) -> None:
        """RENAME/RENAMENX routed to the tier holding the source; the
        destination is cleared in BOTH tiers first (Redis RENAME overwrites
        whatever held that name). Serialized on the dispatcher -> atomic."""
        for op in ops:
            new = op.payload["newkey"]
            in_sketch = bool(self._sketch_side("exists", target))
            in_struct = self.structures.exists(target)
            if not in_sketch and not in_struct:
                op.future.set_exception(KeyError(f"no such key '{target}'"))
                continue
            if op.payload.get("nx") and (
                    bool(self._sketch_side("exists", new))
                    or self.structures.exists(new)):
                op.future.set_result(False)
                continue
            if in_sketch:
                self.structures.delete(new)
                probe = Op(target=target, kind="rename", payload=op.payload)
                # graftlint: allow-journal(fan-out of an already-journaled rename: the executor journaled the original op before calling into this backend, this is tier routing below the commit point)
                self.sketch.run("rename", target, [probe])
                try:
                    # graftlint: allow-block(same-thread: sketch.run above completes the probe future before returning)
                    op.future.set_result(probe.future.result())
                except Exception as exc:  # noqa: BLE001
                    op.future.set_exception(exc)
            else:
                try:
                    self._sketch_side("delete", new)
                    # graftlint: allow-journal(same fan-out: the journaled rename op is forwarded to the structures tier below the commit point)
                    self.structures.run("rename", target, [op])
                except Exception as exc:  # noqa: BLE001
                    # Mirror the sketch branch: a raising tier must not
                    # strand the caller's future (the executor only fails
                    # futures for exceptions that escape backend.run, and
                    # an earlier op in this batch may already be resolved).
                    if not op.future.done():
                        op.future.set_exception(exc)

    def _both_keys(self, target: str, ops: List[Op]) -> None:
        """KEYS across both tiers, serialized on the dispatcher thread."""
        for op in ops:
            pattern = (op.payload or {}).get("pattern", "*")
            op.future.set_result(self.keys(pattern))

    def keys(self, pattern: str = "*") -> List[str]:
        names = getattr(self.sketch, "names", None)
        sketch_keys = names(pattern) if callable(names) else self.sketch.store.keys(pattern)
        seen = dict.fromkeys(sketch_keys)
        for k in self.structures.keys(pattern):
            seen[k] = None
        return list(seen)
