"""Tier E — whole-program op-contract analysis (G019-G022).

Tiers A-D lint single files, jaxprs, threads and event loops; nothing
lints the *distributed op contract* the whole stack hangs off: ~10
per-subsystem kind registries that must agree with ``commands.py``'s
``OP_TABLE`` — geo ``SEMILATTICE_KINDS``/``DESTRUCTIVE_KINDS``/
``SHIP_KINDS``, replica ``READ_KINDS`` + the parked pin set, cluster
``CLUSTER_KINDS``, the delta plane's ``COALESCE_GROUPS``/
``GLOBAL_COALESCE``, the RESP wire command table, the journal's
write-kind coverage, graftlint's own G007 write set, and the backends'
``_op_<kind>`` dispatch tables. A single missed entry silently produces
unjournaled writes, geo divergence, replica-served stale writes, or a
journal that replays into ``unknown op kind`` — exactly the drift class
a fixed-function command contract prevents in hardware sketch engines.

Rules:

  G019 registry-drift — a kind in a subsystem registry that OP_TABLE
       doesn't define; a foldable write kind missing from
       COALESCE_GROUPS; a geo-shipped kind classified both (or neither)
       semilattice and destructive, or not write=True; a geo_* record
       kind in SHIP_KINDS (echo-loop cut violation); a cluster
       ownership kind that isn't a journaled write; the G007 write set
       drifting from OP_TABLE.
  G020 surface-hole — a kind dispatched from the client facade that
       OP_TABLE doesn't define; a facade-reachable read kind the
       replica router can neither route (READ_KINDS) nor pin to the
       primary; a tpu-tier kind with a RESP analogue that the wire
       command table doesn't serve and whose OpDescriptor declares no
       ``engine-only(why)``/``internal(why)`` escape (empty reasons
       don't count).
  G021 replay-safety — a journaled write kind whose declared tiers
       have no replay dispatch path: no ``_op_<kind>`` handler in the
       tier's backend, no RoutingBackend fan-out, no cluster-guard
       interception, or a coord-tier kind with no engine handler to
       replay through.
  G022 arbitration-completeness — a destructive geo kind with no LWW
       arbitration branch in ``GeoApplier.note_local`` (local writes
       would stop arbitrating against remote deletes — silent
       divergence), or a geo_* apply kind with no ``rebuild`` branch
       (restart replay would drop its LWW effect).

Inputs are gathered by importing the live registries (so the lint sees
exactly what the engine executes) plus AST extraction for the tables
that exist only as source patterns (wire staged kinds, facade dispatch
literals, ``_op_*`` handler sets, applier arbitration branches). Every
input is overridable via ``analyze(**overrides)`` so tests can seed
violations without touching the tree.

Suppression: ``# graftlint: allow-contract(reason)`` on the flagged
line (or the line above) suppresses any Tier E rule there; per-rule
aliases (``allow-drift``, ``allow-hole``, ``allow-replay``,
``allow-arbiter`` or ``allow-g019``..``allow-g022``) scope tighter. A
reason is mandatory.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from .findings import Finding

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TIER_E_RULES = ("G019", "G020", "G021", "G022")

#: suppression names honored on a Tier E finding line. "contract" is the
#: tier-wide escape the other tiers don't have: one annotation covers a
#: line that several contract rules anchor to (registry definition lines).
_TIER_WIDE = "contract"

_ITEM_RE = re.compile(r"allow-([A-Za-z0-9_-]+)\(([^)]*)\)")
_ESCAPE_RE = re.compile(r"^(engine-only|internal)\((.+)\)$", re.DOTALL)

#: files AST-extracted (repo-relative)
WIRE_TABLE = "redisson_tpu/wire/commands.py"
APPLIER = "redisson_tpu/geo/applier.py"
DELTA = "redisson_tpu/ingest/delta.py"
OP_TABLE_FILE = "redisson_tpu/commands.py"
ENGINE_FILES = ("redisson_tpu/structures/engine.py",
                "redisson_tpu/structures/extended.py")
TPU_FILE = "redisson_tpu/backend_tpu.py"
FACADE_DIRS = ("redisson_tpu/models",)
FACADE_FILES = ("redisson_tpu/client.py",)

_OP_DEF_RE = re.compile(r"def _op_(\w+)\(")


# ---------------------------------------------------------------------------
# source helpers
# ---------------------------------------------------------------------------


class _Src:
    """One anchorable source file: lines + suppression map."""

    def __init__(self, relpath: str, text: str):
        self.relpath = relpath
        self.lines = text.splitlines()
        self.text = text
        self.allows: Dict[int, Set[str]] = {}
        for i, line in enumerate(self.lines, start=1):
            if "graftlint" not in line:
                continue
            for name, reason in _ITEM_RE.findall(line):
                if reason.strip():
                    self.allows.setdefault(i, set()).add(name.lower())

    def anchor(self, needle: str, default: int = 1) -> int:
        """First 1-based line containing `needle` (the registry entry /
        descriptor the finding is about), so fingerprints track the
        declaration and suppressions sit next to it."""
        for i, line in enumerate(self.lines, start=1):
            if needle in line:
                return i
        return default

    def allowed(self, rule: str, line: int) -> bool:
        names = {rule.lower(), _ALIAS.get(rule, ""), _TIER_WIDE}
        for ln in (line, line - 1):
            if names & self.allows.get(ln, set()):
                return True
        return False


_ALIAS = {"G019": "drift", "G020": "hole", "G021": "replay",
          "G022": "arbiter"}


def _load(repo_root: str, relpath: str) -> Optional[_Src]:
    path = os.path.join(repo_root, relpath)
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as f:
        return _Src(relpath, f.read())


def _def_line(src: _Src, name: str) -> int:
    return src.anchor(f"def {name}(", 1)


# ---------------------------------------------------------------------------
# AST extraction
# ---------------------------------------------------------------------------


def _body_string_consts(src: _Src) -> Set[str]:
    """Every string constant inside function bodies, excluding
    docstrings — the over-approximation used to recover staged op kinds
    from the wire command table (builders compute some kinds via
    conditional expressions, so tuple-literal extraction alone misses
    them). Callers intersect with the OP_TABLE key set."""
    out: Set[str] = set()
    try:
        tree = ast.parse(src.text)
    except SyntaxError:
        return out
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        body = fn.body
        if (body and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            body = body[1:]  # skip the docstring
        for stmt in body:
            for n in ast.walk(stmt):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    out.add(n.value)
    return out


def _facade_kinds(src: _Src) -> Dict[str, int]:
    """kind -> first dispatch line for literal-kind executor calls in a
    facade module (`<x>.execute_async(target, "kind", ...)` and the sync/
    read variants)."""
    out: Dict[str, int] = {}
    try:
        tree = ast.parse(src.text)
    except SyntaxError:
        return out
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if not (isinstance(f, ast.Attribute) and f.attr in (
                "execute_async", "execute_sync", "execute_read")):
            continue
        if len(n.args) < 2:
            continue
        k = n.args[1]
        if isinstance(k, ast.Constant) and isinstance(k.value, str):
            out.setdefault(k.value, k.lineno)
    return out


def _kind_compare_consts(src: _Src, func_name: str,
                         attr: str = "kind") -> Set[str]:
    """String constants compared (==) against a kind expression inside
    the named function/method — `r.kind == "delete"` (the applier's
    arbitration branches) or a bare `kind == "hll_add"` parameter (the
    delta plane's foldable dispatcher)."""
    out: Set[str] = set()
    try:
        tree = ast.parse(src.text)
    except SyntaxError:
        return out

    def is_kind(node) -> bool:
        return ((isinstance(node, ast.Attribute) and node.attr == attr)
                or (isinstance(node, ast.Name) and node.id == attr))

    for fn in ast.walk(tree):
        if not (isinstance(fn, ast.FunctionDef) and fn.name == func_name):
            continue
        for n in ast.walk(fn):
            if not isinstance(n, ast.Compare):
                continue
            sides = [n.left] + list(n.comparators)
            if any(is_kind(s) for s in sides):
                for s in sides:
                    if isinstance(s, ast.Constant) and isinstance(
                            s.value, str):
                        out.add(s.value)
    return out


def _op_handlers(*srcs: Optional[_Src]) -> Set[str]:
    out: Set[str] = set()
    for src in srcs:
        if src is not None:
            out |= set(_OP_DEF_RE.findall(src.text))
    return out


def _foldable_kinds(src: Optional[_Src]) -> FrozenSet[str]:
    """Kinds the delta plane can host-fold: the string constants the
    `foldable()` dispatcher compares against `kind`."""
    if src is None:
        return frozenset()
    return frozenset(_kind_compare_consts(src, "foldable", "kind")
                     ) or frozenset()


# ---------------------------------------------------------------------------
# input gathering
# ---------------------------------------------------------------------------


def gather(repo_root: str = REPO_ROOT) -> dict:
    """Collect the default contract universe: live registries by import,
    source-pattern tables by AST. Every key is an `analyze(**overrides)`
    override point."""
    from redisson_tpu.commands import OP_TABLE
    from redisson_tpu.cluster.shard import CLUSTER_KINDS
    from redisson_tpu.geo.applier import (DESTRUCTIVE_KINDS,
                                          SEMILATTICE_KINDS, SHIP_KINDS)
    from redisson_tpu.replica import router as _replica_router
    from redisson_tpu.routing import RoutingBackend
    from redisson_tpu.backend_tpu import TpuBackend
    from redisson_tpu.executor import PARKED_KINDS
    from .astlint import _write_kinds

    wire_src = _load(repo_root, WIRE_TABLE)
    applier_src = _load(repo_root, APPLIER)
    delta_src = _load(repo_root, DELTA)

    facade: Dict[str, Tuple[str, int]] = {}
    facade_files = list(FACADE_FILES)
    for d in FACADE_DIRS:
        full = os.path.join(repo_root, d)
        if os.path.isdir(full):
            facade_files += [f"{d}/{fn}" for fn in sorted(os.listdir(full))
                             if fn.endswith(".py")]
    facade_srcs = []
    for rel in facade_files:
        src = _load(repo_root, rel)
        if src is None:
            continue
        facade_srcs.append(src)
        for kind, line in _facade_kinds(src).items():
            facade.setdefault(kind, (rel, line))

    wire_kinds = (frozenset(_body_string_consts(wire_src))
                  if wire_src is not None else frozenset())

    return {
        "op_table": OP_TABLE,
        "cluster_kinds": CLUSTER_KINDS,
        "semilattice_kinds": SEMILATTICE_KINDS,
        "destructive_kinds": DESTRUCTIVE_KINDS,
        "ship_kinds": SHIP_KINDS,
        "coalesce_groups": dict(TpuBackend.COALESCE_GROUPS),
        "global_coalesce": frozenset(TpuBackend.GLOBAL_COALESCE),
        "read_kinds": _replica_router.READ_KINDS,
        "pinned_kinds": _replica_router._PINNED_TO_PRIMARY | PARKED_KINDS,
        "lint_write_kinds": _write_kinds(),
        "both_kinds": frozenset(RoutingBackend._BOTH),
        "foldable_kinds": _foldable_kinds(delta_src),
        "wire_kinds": wire_kinds,
        "facade_kinds": facade,
        "engine_handlers": _op_handlers(
            *(_load(repo_root, p) for p in ENGINE_FILES)),
        "tpu_handlers": _op_handlers(_load(repo_root, TPU_FILE)),
        "applier_local_branches": (
            _kind_compare_consts(applier_src, "note_local")
            if applier_src is not None else set()),
        "applier_rebuild_branches": (
            _kind_compare_consts(applier_src, "rebuild")
            if applier_src is not None else set()),
        "sources": {s.relpath: s for s in (
            [wire_src, applier_src, delta_src,
             _load(repo_root, OP_TABLE_FILE),
             _load(repo_root, "redisson_tpu/cluster/shard.py"),
             _load(repo_root, "redisson_tpu/replica/router.py"),
             _load(repo_root, TPU_FILE)]
            + [_load(repo_root, p) for p in ENGINE_FILES]
            + facade_srcs) if s is not None},
    }


# ---------------------------------------------------------------------------
# the checker
# ---------------------------------------------------------------------------


class _Checker:
    def __init__(self, u: dict):
        self.u = u
        self.findings: List[Finding] = []
        self.counts = {r: 0 for r in TIER_E_RULES}
        self.sources: Dict[str, _Src] = u["sources"]
        self._optable_src = self.sources.get(OP_TABLE_FILE)

    # -- emit helpers -------------------------------------------------------

    def _emit(self, rule: str, relpath: str, line: int, message: str,
              hint: str = "") -> None:
        src = self.sources.get(relpath)
        if src is not None and src.allowed(rule, line):
            return
        self.counts[rule] += 1
        self.findings.append(Finding(rule, relpath, line, message, hint))

    def _emit_at_kind(self, rule: str, kind: str, message: str,
                      hint: str = "") -> None:
        """Anchor a per-kind contract finding at its OP_TABLE descriptor
        line — the single place the fix lands."""
        src = self._optable_src
        line = src.anchor(f'_d("{kind}"') if src is not None else 1
        self._emit(rule, OP_TABLE_FILE, line, message, hint)

    def _emit_registry(self, rule: str, relpath: str, kind: str,
                       fallback_needle: str, message: str,
                       hint: str = "") -> None:
        src = self.sources.get(relpath)
        line = 1
        if src is not None:
            line = src.anchor(f'"{kind}"', src.anchor(fallback_needle))
        self._emit(rule, relpath, line, message, hint)

    # -- G019: registry drift -----------------------------------------------

    def check_registry_drift(self) -> None:
        u = self.u
        table = u["op_table"]
        write = {k for k, d in table.items() if d.write}

        registries = [
            ("cluster CLUSTER_KINDS", u["cluster_kinds"],
             "redisson_tpu/cluster/shard.py", "CLUSTER_KINDS"),
            ("geo SEMILATTICE_KINDS", u["semilattice_kinds"],
             APPLIER, "SEMILATTICE_KINDS"),
            ("geo DESTRUCTIVE_KINDS", u["destructive_kinds"],
             APPLIER, "DESTRUCTIVE_KINDS"),
            ("delta COALESCE_GROUPS", u["coalesce_groups"],
             TPU_FILE, "COALESCE_GROUPS"),
            ("GLOBAL_COALESCE", u["global_coalesce"],
             TPU_FILE, "GLOBAL_COALESCE"),
            ("replica READ_KINDS", u["read_kinds"],
             "redisson_tpu/replica/router.py", "READ_KINDS"),
        ]
        # The wire table's staged kinds are recovered as a string-constant
        # over-approximation (arg names, error text, ...), so it cannot
        # join the undefined-kind sweep above; wire coverage is checked
        # from the OP_TABLE side by G020 instead.
        for name, kinds, relpath, needle in registries:
            for kind in sorted(set(kinds) - set(table)):
                self._emit_registry(
                    "G019", relpath, kind, needle,
                    f"kind '{kind}' in the {name} registry is not defined "
                    f"in OP_TABLE — the op vocabulary and the subsystem "
                    f"have drifted apart",
                    "add the kind to redisson_tpu/commands.py OP_TABLE "
                    "(or remove the stale registry entry)")

        # G007's write set must BE the OP_TABLE write set. The derivation
        # is registry-driven today; this pins it against a hand-edit.
        if u["lint_write_kinds"] and set(u["lint_write_kinds"]) != write:
            drifted = sorted(set(u["lint_write_kinds"]) ^ write)
            self._emit(
                "G019", "tools/graftlint/astlint.py",
                self._lint_write_line(),
                f"graftlint's G007 write-kind set drifted from OP_TABLE "
                f"(disagrees on: {', '.join(drifted[:6])}"
                f"{', ...' if len(drifted) > 6 else ''}) — journal-bypass "
                f"linting no longer matches what the journal records",
                "derive the G007 set from OP_TABLE (astlint._write_kinds)")

        # Foldable write kinds must coalesce: a foldable kind outside
        # COALESCE_GROUPS dispatches one run per op instead of riding the
        # fused delta window — silent multi-launch regression.
        for kind in sorted((u["foldable_kinds"] & write)
                           - set(u["coalesce_groups"])):
            self._emit_registry(
                "G019", TPU_FILE, kind, "COALESCE_GROUPS",
                f"write kind '{kind}' is delta-plane foldable "
                f"(ingest/delta.foldable) but missing from COALESCE_GROUPS "
                f"— its windows never join the fused delta-merge launch",
                "add the kind to TpuBackend.COALESCE_GROUPS")

        # Geo classification: exactly one of semilattice/destructive, the
        # union IS the ship set, every shipped kind is a journaled write,
        # and no geo_* record kind ships (the echo-loop cut).
        for kind in sorted(u["semilattice_kinds"] & u["destructive_kinds"]):
            self._emit_registry(
                "G019", APPLIER, kind, "SEMILATTICE_KINDS",
                f"geo kind '{kind}' is classified BOTH semilattice and "
                f"destructive — sites would arbitrate it inconsistently",
                "a kind is a join or an LWW overwrite, never both")
        for kind in sorted(set(u["ship_kinds"])
                           - set(u["semilattice_kinds"])
                           - set(u["destructive_kinds"])):
            self._emit_registry(
                "G019", APPLIER, kind, "SHIP_KINDS",
                f"geo-shipped kind '{kind}' is classified neither "
                f"semilattice nor destructive — the SiteLink would ship a "
                f"record the applier has no arbitration rule for",
                "classify it in SEMILATTICE_KINDS or DESTRUCTIVE_KINDS")
        for kind in sorted(set(u["ship_kinds"]) & set(table)):
            if not table[kind].write:
                self._emit_registry(
                    "G019", APPLIER, kind, "SHIP_KINDS",
                    f"geo-shipped kind '{kind}' is not write=True in "
                    f"OP_TABLE — it never journals, so the SiteLink (a "
                    f"journal tail) can never ship it",
                    "shipped kinds must be journaled writes")
        for kind in sorted(k for k in u["ship_kinds"]
                           if k.startswith("geo_")):
            self._emit_registry(
                "G019", APPLIER, kind, "SHIP_KINDS",
                f"geo record kind '{kind}' is in SHIP_KINDS — remote "
                f"applies would re-ship, breaking the full-mesh echo-loop "
                f"cut (infinite replication loop)",
                "geo_* records stay site-local by design")

        for kind in sorted(set(u["cluster_kinds"]) & set(table)):
            if not table[kind].write:
                self._emit_registry(
                    "G019", "redisson_tpu/cluster/shard.py", kind,
                    "CLUSTER_KINDS",
                    f"cluster ownership kind '{kind}' is not write=True in "
                    f"OP_TABLE — slot transitions must journal or crash "
                    f"recovery rebuilds a different ownership history",
                    "ownership transitions are journaled writes")

        for kind in sorted(set(u["coalesce_groups"]) & set(table)):
            if not table[kind].write:
                self._emit_registry(
                    "G019", TPU_FILE, kind, "COALESCE_GROUPS",
                    f"read kind '{kind}' is in COALESCE_GROUPS — the delta "
                    f"plane folds write payloads; a read has nothing to "
                    f"fold and would retire with no result",
                    "only foldable write kinds belong in COALESCE_GROUPS")

    def _lint_write_line(self) -> int:
        src = self.sources.get("tools/graftlint/astlint.py")
        return src.anchor("def _write_kinds") if src is not None else 1

    # -- G020: surface holes -------------------------------------------------

    def check_surface_holes(self) -> None:
        u = self.u
        table = u["op_table"]
        for kind, (relpath, line) in sorted(u["facade_kinds"].items()):
            if kind in table:
                continue
            self._emit(
                "G020", relpath, line,
                f"facade dispatches kind '{kind}' that OP_TABLE does not "
                f"define — the executor will raise 'unknown op kind' and "
                f"the completeness tests never saw it",
                "declare the kind in redisson_tpu/commands.py")
        for kind, (relpath, line) in sorted(u["facade_kinds"].items()):
            d = table.get(kind)
            if d is None or d.write:
                continue
            if kind in u["read_kinds"] or kind in u["pinned_kinds"]:
                continue
            self._emit(
                "G020", relpath, line,
                f"facade read kind '{kind}' is neither replica-routable "
                f"(READ_KINDS) nor pinned to the primary — the replica "
                f"router cannot classify it",
                "fix the READ_KINDS derivation or pin the kind")
        for kind, d in sorted(table.items()):
            if "tpu" not in d.tiers or d.redis_name == "-":
                continue
            if kind in u["wire_kinds"]:
                continue
            m = _ESCAPE_RE.match(d.contract or "")
            if m is not None and m.group(2).strip():
                continue
            self._emit_at_kind(
                "G020", kind,
                f"tpu-tier kind '{kind}' ({d.redis_name}) is not served by "
                f"the wire command table and declares no contract escape — "
                f"stock RESP clients cannot reach it and nothing says "
                f"that's intentional",
                "map it in wire/commands.py ENGINE_COMMANDS or annotate "
                "the OpDescriptor: contract='engine-only(<why>)' / "
                "'internal(<why>)'")

    # -- G021: replay safety -------------------------------------------------

    def check_replay_safety(self) -> None:
        u = self.u
        table = u["op_table"]
        dispatchable = u["both_kinds"]
        for kind, d in sorted(table.items()):
            if not d.write:
                continue
            missing: List[str] = []
            if "engine" in d.tiers and kind not in (
                    u["engine_handlers"] | dispatchable):
                missing.append("structures engine (_op_%s)" % kind)
            if "tpu" in d.tiers and kind not in (
                    u["tpu_handlers"] | dispatchable):
                missing.append("tpu backend (_op_%s)" % kind)
            if "coord" in d.tiers and "engine" not in d.tiers:
                missing.append("engine tier (coord kinds replay through "
                               "the engine interpreter)")
            if d.tiers == frozenset({"cluster"}) and kind not in u[
                    "cluster_kinds"]:
                missing.append("cluster guard (CLUSTER_KINDS interception)")
            if not missing:
                continue
            self._emit_at_kind(
                "G021", kind,
                f"journaled write kind '{kind}' has no replay dispatch "
                f"path in: {'; '.join(missing)} — crash recovery and "
                f"followers replay the journal through "
                f"executor.execute_async, which would raise 'unknown op "
                f"kind' and drop the write",
                "register the handler (or fix the kind's declared tiers)")

    # -- G022: arbitration completeness --------------------------------------

    def check_arbitration(self) -> None:
        u = self.u
        src = self.sources.get(APPLIER)
        for kind in sorted(set(u["destructive_kinds"])
                           - set(u["applier_local_branches"])):
            line = _def_line(src, "note_local") if src is not None else 1
            self._emit(
                "G022", APPLIER, line,
                f"destructive kind '{kind}' has no LWW arbitration branch "
                f"in GeoApplier.note_local — local '{kind}' writes never "
                f"advance the floor stamps, so a remote write that LOST "
                f"to it would still apply (silent cross-site divergence)",
                "add the kind's floor/lw branch to note_local")
        geo_apply = sorted(k for k in u["op_table"] if k.startswith("geo_"))
        for kind in geo_apply:
            if kind in u["applier_rebuild_branches"]:
                continue
            line = _def_line(src, "rebuild") if src is not None else 1
            self._emit(
                "G022", APPLIER, line,
                f"geo apply kind '{kind}' has no branch in "
                f"GeoApplier.rebuild — restart replay would drop its LWW "
                f"effect and the site re-arbitrates history differently "
                f"than it did live",
                "add the kind to the rebuild stamp fold")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def analyze(repo_root: str = REPO_ROOT, **overrides
            ) -> Tuple[List[Finding], Dict[str, List[str]], dict]:
    """Run Tier E. Returns (findings, {relpath: source lines} for
    fingerprinting, tier_e stats block). Any `gather()` key can be
    overridden — the seeded-violation path for tests.

    Tier E is whole-program over THE engine tree: when `repo_root`
    doesn't hold the OP_TABLE (scratch-dir lint runs), there is no
    contract to check and the tier reports empty."""
    if _load(repo_root, OP_TABLE_FILE) is None and "op_table" not in overrides:
        empty = {"rules": {r: 0 for r in TIER_E_RULES}, "kinds": 0,
                 "write_kinds": 0, "surfaces": {}, "declared_cells": 0}
        return [], {}, empty
    u = gather(repo_root)
    extra_sources = overrides.pop("sources", None)
    u.update(overrides)
    if extra_sources:
        u["sources"] = {**u["sources"], **extra_sources}
    chk = _Checker(u)
    chk.check_registry_drift()
    chk.check_surface_holes()
    chk.check_replay_safety()
    chk.check_arbitration()
    sources = {rel: src.lines for rel, src in chk.sources.items()}
    table = u["op_table"]
    stats = {
        "rules": dict(chk.counts),
        "kinds": len(table),
        "write_kinds": sum(1 for d in table.values() if d.write),
        "surfaces": {
            "wire": len(u["wire_kinds"] & set(table)),
            "facade": len(set(u["facade_kinds"]) & set(table)),
            "geo_ship": len(u["ship_kinds"]),
            "replay_handlers": len(u["engine_handlers"]
                                   | u["tpu_handlers"]),
        },
        "declared_cells": sum(len(v) for v in
                              declared_cells(universe=u).values()),
    }
    return chk.findings, sources, stats


def declared_cells(repo_root: str = REPO_ROOT,
                   universe: Optional[dict] = None) -> Dict[str, List[str]]:
    """The static (surface -> write kinds) coverage matrix the runtime
    contract witness is diffed against (`suite.py --contract-smoke`):

      wire   — write kinds the RESP command table stages
      geo    — the geo_* apply kinds remote arbitration dispatches
      facade — the delta-plane write trio every distributed subsystem
               (journal, delta window, geo ship set, replica
               stream) must agree on

    The replay surface is intentionally dynamic: its declared set is the
    kind population of the smoke's own journal.
    """
    u = universe if universe is not None else gather(repo_root)
    table = u["op_table"]
    write = {k for k, d in table.items() if d.write}
    return {
        "wire": sorted(u["wire_kinds"] & write),
        "geo": sorted(k for k in table if k.startswith("geo_")),
        "facade": sorted(set(u["semilattice_kinds"]) & write),
    }
