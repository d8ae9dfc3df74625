"""graftlint as a tier-1 gate + unit coverage for its rules.

Three layers:

1. Gate tests — the committed tree must be clean under both tiers (Tier A
   AST rules over ``redisson_tpu/`` and the Tier B jaxpr audit of ``ops/``),
   with an empty baseline. A regression that introduces an unchunked int32
   reduction, a hidden host sync, or an x64 leak fails CI here.
2. Rule unit tests — each rule is exercised on small seeded sources via
   ``FileLinter(source=...)`` so detection (and non-detection of the blessed
   idioms) is pinned independently of the repo's current contents.
3. Plumbing — suppression comments, baseline roundtrip, and the module CLI.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.graftlint import run_lint
from tools.graftlint.astlint import FileLinter
from tools.graftlint import baseline as baseline_mod
from tools.graftlint.findings import RULES, SUPPRESS_ALIASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE_DIR = os.path.join(REPO, "redisson_tpu")


def lint_src(src, filename="scratch.py", explicit=True):
    """Lint an in-memory source string with full rule coverage."""
    return FileLinter(filename, repo_root=None, explicit=explicit,
                      source=textwrap.dedent(src)).run()


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# 1. gate: the committed tree is clean
# ---------------------------------------------------------------------------

def test_repo_tier_a_clean():
    dicts = run_lint([ENGINE_DIR], jaxpr=False)
    assert dicts == [], (
        "graftlint Tier A findings in redisson_tpu/ — fix or suppress with "
        "a reasoned `# graftlint: allow-<rule>(why)` comment:\n"
        + "\n".join(f"{d['file']}:{d['line']} {d['rule']} {d['message']}"
                    for d in dicts)
    )


def test_jaxpr_audit_clean():
    from tools.graftlint.jaxpr_audit import run_audits

    findings = run_audits()
    assert findings == [], (
        "jaxpr audit findings:\n"
        + "\n".join(f"{f.file} {f.rule} {f.message}" for f in findings)
    )


def test_jaxpr_registry_covers_public_ops():
    """Every public function in the audited ops modules is either traced by
    the registry or explicitly declared host-side in HOST_SIDE."""
    import importlib
    import inspect

    from tools.graftlint.jaxpr_audit import HOST_SIDE, build_registry

    audited = {}  # module short name -> set of audited fn names
    for name, _thunk, _allow in build_registry():
        mod, _, fn = name.partition(".")
        audited.setdefault(mod, set()).add(fn.split("(")[0])
    # pallas wrappers in the registry are named "pallas.*"
    audited["pallas_kernels"] = audited.pop("pallas", set())

    missing = []
    for short in ["bitset", "bloom", "hll", "hashing", "u64"]:
        mod = importlib.import_module(f"redisson_tpu.ops.{short}")
        for fname, fn in vars(mod).items():
            if fname.startswith("_") or not inspect.isfunction(fn):
                continue
            if getattr(fn, "__module__", None) != mod.__name__:
                continue
            if fname.endswith("_jit"):  # jit alias of an audited base fn
                continue
            if fname in HOST_SIDE.get(short, set()):
                continue
            if fname not in audited.get(short, set()):
                missing.append(f"{short}.{fname}")
    assert not missing, (
        "public ops with no jaxpr-audit registry entry (add one in "
        f"tools/graftlint/jaxpr_audit.py or list in HOST_SIDE): {missing}"
    )


def test_baseline_is_empty():
    path = os.path.join(REPO, "tools", "graftlint", "baseline.json")
    assert baseline_mod.load(path) == set(), (
        "the committed baseline must stay empty — fix findings instead of "
        "grandfathering them"
    )


# ---------------------------------------------------------------------------
# 2. rule unit tests on seeded sources
# ---------------------------------------------------------------------------

def test_g001_unchunked_int_reduction():
    findings = lint_src("""
        import jax.numpy as jnp

        def total(bits):
            return jnp.sum(bits.astype(jnp.int32))
    """)
    assert [f.rule for f in findings] == ["G001"]
    assert findings[0].line == 5


def test_g001_chunk_partials_idiom_ok():
    findings = lint_src("""
        import jax.numpy as jnp

        def partials(chunks):
            return jnp.sum(chunks.astype(jnp.int32), axis=1)

        def total(x):
            return jnp.sum(x.astype(jnp.float32))
    """)
    assert findings == []  # axis= reduction and float reduction both fine


def test_g002_host_sync_on_device_value():
    findings = lint_src("""
        import jax.numpy as jnp

        def count(bits):
            return int(jnp.sum(bits, axis=0)[0])
    """)
    assert "G002" in rules_of(findings)


def test_g002_scoped_to_dispatch_paths():
    src = """
        import jax.numpy as jnp

        def count(bits):
            return int(jnp.max(bits, axis=0))
    """
    # engine.py is in the sync-sensitive scope; models/ is not.
    hot = FileLinter(os.path.join(REPO, "redisson_tpu", "engine.py"),
                     repo_root=REPO, source=textwrap.dedent(src)).run()
    cold = FileLinter(os.path.join(REPO, "redisson_tpu", "models", "foo.py"),
                      repo_root=REPO, source=textwrap.dedent(src)).run()
    assert "G002" in rules_of(hot)
    assert "G002" not in rules_of(cold)


def test_g002_one_hop_name_provenance():
    """`x = engine_call(...); int(x)` is flagged, not just direct nesting —
    the shape the pipelined executor's staging code must never contain."""
    findings = lint_src("""
        import jax.numpy as jnp

        def count(bits):
            est = jnp.sum(bits, axis=0)
            return int(est)
    """)
    assert "G002" in rules_of(findings)


def test_g002_provenance_host_assignment_ok():
    """A Name assigned from host-only math does not trip the hop."""
    findings = lint_src("""
        import jax.numpy as jnp

        def count(bits):
            est = len(bits) * 2
            return int(est)
    """)
    assert "G002" not in rules_of(findings)


def test_g002_executor_in_sync_scope():
    """executor.py staging code is now inside the G002 scope."""
    src = """
        import jax.numpy as jnp

        def stage(bits):
            return int(jnp.max(bits, axis=0))
    """
    hot = FileLinter(os.path.join(REPO, "redisson_tpu", "executor.py"),
                     repo_root=REPO, source=textwrap.dedent(src)).run()
    assert "G002" in rules_of(hot)


def test_g003_python_scalar_missing_static():
    findings = lint_src("""
        import jax

        @jax.jit
        def scale(x, n: int):
            return x * n
    """)
    assert "G003" in rules_of(findings)


def test_g003_static_argnames_ok():
    findings = lint_src("""
        import functools

        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def scale(x, n: int):
            return x * n
    """)
    assert findings == []


def test_g003_jit_constructed_per_call():
    findings = lint_src("""
        import jax

        def hot_loop(xs):
            f = jax.jit(lambda x: x + 1)
            return [f(x) for x in xs]
    """)
    assert "G003" in rules_of(findings)


def test_g004_raw_lane_arithmetic():
    findings = lint_src("""
        def widen(x):
            return (x.hi << 32) | x.lo
    """)
    assert "G004" in rules_of(findings)


def test_g004_big_literal_in_jax_module():
    findings = lint_src("""
        import jax.numpy as jnp

        def mask(x):
            return x & 0x1FFFFFFFF
    """)
    assert "G004" in rules_of(findings)


def test_g004_allowed_inside_u64_module():
    findings = FileLinter(
        "redisson_tpu/ops/u64.py",
        source="def shl(x):\n    return x.hi << 1\n").run()
    assert "G004" not in rules_of(findings)


def test_g005_pallas_call_contract():
    findings = lint_src("""
        from jax.experimental import pallas as pl

        def run(x):
            return pl.pallas_call(kernel, out_shape=shape)(x)
    """)
    assert "G005" in rules_of(findings)  # interpret= missing


def test_g006_unbounded_future_result():
    findings = lint_src("""
        def wait_all(futures):
            return [f.result() for f in futures]
    """)
    assert "G006" in rules_of(findings)


def test_g006_timeout_bounded_result_ok():
    findings = lint_src("""
        def wait_all(futures):
            return [f.result(timeout=30) for f in futures]
    """)
    assert "G006" not in rules_of(findings)


def test_g006_scoped_to_dispatch_and_serve_paths():
    src = """
        def wait(f):
            return f.result()
    """
    hot = FileLinter(
        os.path.join(REPO, "redisson_tpu", "serve", "scheduler.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    hot2 = FileLinter(
        os.path.join(REPO, "redisson_tpu", "executor.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    cold = FileLinter(
        os.path.join(REPO, "redisson_tpu", "models", "foo.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    assert "G006" in rules_of(hot)
    assert "G006" in rules_of(hot2)
    assert "G006" not in rules_of(cold)


def test_g006_g009_scoped_to_wire():
    """The wire tier is hot-path: an untimed .result() would park the event
    loop for every connection, and a time.time() stamp would poison the
    admitted_at duration math — both scopes cover redisson_tpu/wire/."""
    block_src = """
        def wait(f):
            return f.result()
    """
    clock_src = """
        import time

        def stamp():
            return time.time()
    """
    wire = os.path.join(REPO, "redisson_tpu", "wire", "server.py")
    blocked = FileLinter(wire, repo_root=REPO,
                         source=textwrap.dedent(block_src)).run()
    clocked = FileLinter(wire, repo_root=REPO,
                         source=textwrap.dedent(clock_src)).run()
    assert "G006" in rules_of(blocked)
    assert "G009" in rules_of(clocked)


def test_g006_g009_scoped_to_geo():
    """geo/ link and applier threads sit between the journal and the
    dispatcher: an untimed .result() there wedges replication behind one
    slow apply, and a time.time() lag stamp would let NTP slew corrupt
    staleness math — both scopes must cover redisson_tpu/geo/."""
    block_src = """
        def wait(f):
            return f.result()
    """
    clock_src = """
        import time

        def stamp():
            return time.time()
    """
    geo = os.path.join(REPO, "redisson_tpu", "geo", "newfile.py")
    blocked = FileLinter(geo, repo_root=REPO,
                         source=textwrap.dedent(block_src)).run()
    clocked = FileLinter(geo, repo_root=REPO,
                         source=textwrap.dedent(clock_src)).run()
    assert "G006" in rules_of(blocked)
    assert "G009" in rules_of(clocked)


def test_g006_suppression_with_reason():
    findings = lint_src("""
        def wait(f):
            # graftlint: allow-g006(done-callback: f is already resolved)
            return f.result()
    """)
    assert "G006" not in rules_of(findings)


def test_g007_literal_write_run_flagged():
    findings = lint_src("""
        def evict(backend, target, ops):
            backend.run("delete", target, ops)
    """)
    assert "G007" in rules_of(findings)


def test_g007_read_kind_not_flagged():
    findings = lint_src("""
        def peek(backend, target, ops):
            backend.run("exists", target, ops)
            backend.run("hll_export", target, ops)
    """)
    assert "G007" not in rules_of(findings)


def test_g007_variable_kind_not_flagged():
    """The executor's own dispatch (`run(kind, ...)` with a variable) is the
    sanctioned path — only literal kinds are a bypass signature."""
    findings = lint_src("""
        def dispatch(backend, kind, target, ops):
            backend.run(kind, target, ops)
    """)
    assert "G007" not in rules_of(findings)


def test_g007_scoped_outside_executor():
    src = """
        def evict(backend, target, ops):
            backend.run("delete", target, ops)
    """
    hot = FileLinter(os.path.join(REPO, "redisson_tpu", "routing.py"),
                     repo_root=REPO, source=textwrap.dedent(src)).run()
    commit_point = FileLinter(
        os.path.join(REPO, "redisson_tpu", "executor.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    outside = FileLinter(os.path.join(REPO, "benchmarks", "bench.py"),
                         repo_root=REPO, source=textwrap.dedent(src)).run()
    assert "G007" in rules_of(hot)
    assert "G007" not in rules_of(commit_point)
    assert "G007" not in rules_of(outside)


def test_g007_suppression_with_reason():
    findings = lint_src("""
        def evict(backend, target, ops):
            # graftlint: allow-journal(below the commit point: delegate fan-out)
            backend.run("delete", target, ops)
    """)
    assert "G007" not in rules_of(findings)


def test_g007_covers_cluster_write_kinds():
    """The slot-migration kinds are write=True in OP_TABLE, so G007's
    registry-derived write set must flag a direct `.run()` of any of them
    — ownership changes that bypass the journal would silently diverge on
    recovery (tests/test_cluster.py proves the replay depends on them)."""
    from redisson_tpu.cluster.shard import CLUSTER_KINDS
    from tools.graftlint.astlint import _write_kinds

    assert CLUSTER_KINDS <= _write_kinds()
    for kind in sorted(CLUSTER_KINDS):
        findings = lint_src(f"""
            def flip(backend, ops):
                backend.run("{kind}", "", ops)
        """)
        assert "G007" in rules_of(findings), kind


def lint_scoped(src, filename="redisson_tpu/executor.py"):
    """Lint an in-memory source under an in-repo relpath (G008 and the
    other scope-gated rules key on the repo-relative location)."""
    return FileLinter(os.path.join(REPO, filename), repo_root=REPO,
                      source=textwrap.dedent(src)).run()


def test_g008_broad_except_without_classify_flagged():
    for handler in ("except Exception as exc:", "except BaseException:",
                    "except:"):
        findings = lint_scoped(f"""
            def complete(ops):
                try:
                    launch(ops)
                {handler}
                    for op in ops:
                        op.future.set_exception(ValueError("boom"))
        """)
        assert "G008" in rules_of(findings), handler


def test_g008_classify_in_body_ok():
    findings = lint_scoped("""
        from redisson_tpu.fault.taxonomy import classify

        def complete(ops):
            try:
                launch(ops)
            except Exception as exc:
                exc = classify(exc, seam="kernel_launch")
                for op in ops:
                    op.future.set_exception(exc)
    """)
    assert "G008" not in rules_of(findings)
    # attribute form too (taxonomy.classify)
    findings = lint_scoped("""
        from redisson_tpu.fault import taxonomy

        def complete(ops):
            try:
                launch(ops)
            except Exception as exc:
                raise taxonomy.classify(exc, seam="d2h_complete")
    """, filename="redisson_tpu/backend_tpu.py")
    assert "G008" not in rules_of(findings)


def test_g008_narrow_except_not_flagged():
    findings = lint_scoped("""
        def load(path):
            try:
                return open(path).read()
            except (OSError, ValueError):
                return None
    """, filename="redisson_tpu/persist/journal.py")
    assert "G008" not in rules_of(findings)


def test_g008_scoped_to_fault_boundaries():
    src = """
        def f(ops):
            try:
                g(ops)
            except Exception:
                pass
    """
    in_scope = [
        os.path.join(REPO, "redisson_tpu", "executor.py"),
        os.path.join(REPO, "redisson_tpu", "backend_tpu.py"),
        os.path.join(REPO, "redisson_tpu", "persist", "journal.py"),
        os.path.join(REPO, "redisson_tpu", "parallel", "backend_pod.py"),
    ]
    out_of_scope = [
        os.path.join(REPO, "redisson_tpu", "models", "foo.py"),
        os.path.join(REPO, "redisson_tpu", "serve", "scheduler.py"),
        os.path.join(REPO, "redisson_tpu", "interop", "backend_redis.py"),
    ]
    for path in in_scope:
        findings = FileLinter(path, repo_root=REPO,
                              source=textwrap.dedent(src)).run()
        assert "G008" in rules_of(findings), path
    for path in out_of_scope:
        findings = FileLinter(path, repo_root=REPO,
                              source=textwrap.dedent(src)).run()
        assert "G008" not in rules_of(findings), path
    # `explicit` (a directly-named CLI target, e.g. bench.py) must NOT
    # enable G008: outside the fault boundary a broad except is usually
    # deliberate best-effort isolation, not a taxonomy leak.
    findings = FileLinter(os.path.join(REPO, "bench.py"), repo_root=REPO,
                          explicit=True, source=textwrap.dedent(src)).run()
    assert "G008" not in rules_of(findings)


def test_g008_suppression_with_reason():
    findings = lint_scoped("""
        def f(ops):
            try:
                g(ops)
            except Exception:
                # graftlint: allow-bare(thread-isolation backstop: closures own their futures)
                pass
    """)
    assert "G008" not in rules_of(findings)


def test_g009_wallclock_in_latency_path_flagged():
    findings = lint_src("""
        import time

        def measure(op):
            t0 = time.time()
            op()
            return time.time() - t0
    """)
    assert "G009" in rules_of(findings)


def test_g009_from_import_alias_flagged():
    findings = lint_src("""
        from time import time as now

        def measure(op):
            t0 = now()
            op()
            return now() - t0
    """)
    assert "G009" in rules_of(findings)


def test_g009_monotonic_ok():
    findings = lint_src("""
        import time

        def measure(op):
            t0 = time.monotonic()
            op()
            return time.monotonic() - t0
    """)
    assert "G009" not in rules_of(findings)


def test_g009_scoped_to_latency_paths():
    src = """
        import time

        def stamp():
            return time.time()
    """
    trace = FileLinter(
        os.path.join(REPO, "redisson_tpu", "trace", "spans.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    persist = FileLinter(
        os.path.join(REPO, "redisson_tpu", "persist", "journal.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    serve = FileLinter(
        os.path.join(REPO, "redisson_tpu", "serve", "scheduler.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    cold = FileLinter(
        os.path.join(REPO, "redisson_tpu", "models", "foo.py"),
        repo_root=REPO, source=textwrap.dedent(src)).run()
    assert "G009" in rules_of(trace)
    assert "G009" in rules_of(persist)
    assert "G009" in rules_of(serve)
    assert "G009" not in rules_of(cold)


def test_g009_suppression_with_reason():
    findings = lint_src("""
        import time

        def stamp():
            return time.time()  # graftlint: allow-wallclock(display-only entry timestamp)
    """)
    assert "G009" not in rules_of(findings)


def test_g010_objects_mutation_flagged():
    """Every direct `._objects` mutation shape is a ledger bypass."""
    shapes = [
        'store._objects[name] = obj',
        'del store._objects[name]',
        'store._objects.pop(name, None)',
        'store._objects.clear()',
        'store._objects.update(other)',
        'store._objects.setdefault(name, obj)',
    ]
    for stmt in shapes:
        findings = lint_src(f"def f(store, name, obj, other):\n    {stmt}\n")
        assert "G010" in rules_of(findings), stmt


def test_g010_device_put_to_state_flagged():
    findings = lint_src("""
        import jax

        def install(obj, host):
            obj.state = jax.device_put(host)
    """)
    assert "G010" in rules_of(findings)
    # nested inside an expression too
    findings = lint_src("""
        import jax

        def install(obj, host, mask):
            obj.state = jax.device_put(host) * mask
    """)
    assert "G010" in rules_of(findings)


def test_g010_accounted_idioms_not_flagged():
    # device_put routed through the store seam (the sanctioned shape)
    findings = lint_src("""
        import jax

        def load(store, name, host):
            arr = jax.device_put(host)
            store.get_or_create(name, "hll", lambda: arr)
    """)
    assert "G010" not in rules_of(findings)
    # host-side .state assignment (no device bytes involved)
    findings = lint_src("""
        def reset(self):
            self.state = ClusterState()
    """)
    assert "G010" not in rules_of(findings)
    # read access to ._objects is fine; only mutation is a bypass
    findings = lint_src("""
        def peek(store, name):
            return store._objects.get(name)
    """)
    assert "G010" not in rules_of(findings)


def test_g010_scoped_outside_accounted_seams():
    src = """
        import jax

        def f(store, name, obj, host):
            store._objects[name] = obj
            obj.state = jax.device_put(host)
    """
    in_scope = [
        os.path.join(REPO, "redisson_tpu", "client.py"),
        os.path.join(REPO, "redisson_tpu", "serve", "scheduler.py"),
        os.path.join(REPO, "redisson_tpu", "interop", "fake_server.py"),
    ]
    out_of_scope = [
        os.path.join(REPO, "redisson_tpu", "store.py"),
        os.path.join(REPO, "redisson_tpu", "backend_tpu.py"),
        os.path.join(REPO, "redisson_tpu", "parallel", "backend_pod.py"),
        os.path.join(REPO, "redisson_tpu", "memstat", "accounting.py"),
        os.path.join(REPO, "benchmarks", "bench.py"),
    ]
    for path in in_scope:
        findings = FileLinter(path, repo_root=REPO,
                              source=textwrap.dedent(src)).run()
        assert "G010" in rules_of(findings), path
    for path in out_of_scope:
        findings = FileLinter(path, repo_root=REPO,
                              source=textwrap.dedent(src)).run()
        assert "G010" not in rules_of(findings), path


def test_g010_suppression_with_reason():
    findings = lint_src("""
        def evict(store, name):
            # graftlint: allow-mem(recovery path: ledger rebuilt wholesale after replay)
            store._objects.pop(name, None)
    """)
    assert "G010" not in rules_of(findings)


def test_g010_registry_coverage():
    assert "G010" in RULES
    alias, _desc = RULES["G010"]
    assert alias == "mem"
    assert SUPPRESS_ALIASES["mem"] == "G010"
    assert SUPPRESS_ALIASES["g010"] == "G010"


def test_g007_registry_coverage():
    """Every OP_TABLE kind behaves per its write flag: all write kinds are
    flagged when dispatched as a literal `.run`, no read kind ever is. Pins
    the rule to the registry so new commands are covered automatically."""
    from redisson_tpu.commands import OP_TABLE

    write_kinds = {k for k, d in OP_TABLE.items() if d.write}
    read_kinds = set(OP_TABLE) - write_kinds
    assert len(write_kinds) > 50  # sanity: the registry actually loaded

    def flagged(kind):
        src = f'def f(b, t, ops):\n    b.run("{kind}", t, ops)\n'
        return "G007" in rules_of(lint_src(src))

    missed = sorted(k for k in write_kinds if not flagged(k))
    spurious = sorted(k for k in read_kinds if flagged(k))
    assert missed == [], f"write kinds not flagged by G007: {missed}"
    assert spurious == [], f"read kinds wrongly flagged by G007: {spurious}"


def test_serve_package_lints_clean():
    dicts = run_lint([os.path.join(ENGINE_DIR, "serve")], jaxpr=False)
    assert dicts == [], dicts


def test_g005_blockspec_index_map_arity():
    findings = lint_src("""
        from jax.experimental import pallas as pl

        def run(x, shape):
            grid = (4, 4)
            return pl.pallas_call(
                kernel,
                grid=grid,
                in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
                out_shape=shape,
                interpret=False,
            )(x)
    """)
    assert "G005" in rules_of(findings)  # lambda i: ... under a 2-d grid


# ---------------------------------------------------------------------------
# 3. suppressions, baseline, CLI
# ---------------------------------------------------------------------------

def test_suppression_with_reason():
    findings = lint_src("""
        import jax.numpy as jnp

        def total(bits):
            # graftlint: allow-int-reduce(bounded by construction in this test)
            return jnp.sum(bits.astype(jnp.int32))
    """)
    assert findings == []


def test_suppression_without_reason_is_ignored():
    findings = lint_src("""
        import jax.numpy as jnp

        def total(bits):
            # graftlint: allow-int-reduce()
            return jnp.sum(bits.astype(jnp.int32))
    """)
    assert "G001" in rules_of(findings)


def test_suppression_wrong_rule_does_not_mask():
    findings = lint_src("""
        import jax.numpy as jnp

        def total(bits):
            # graftlint: allow-sync(wrong rule for this line)
            return jnp.sum(bits.astype(jnp.int32))
    """)
    assert "G001" in rules_of(findings)


def test_every_rule_has_a_suppression_alias():
    for rid, (alias, _desc) in RULES.items():
        assert SUPPRESS_ALIASES[alias] == rid
        assert SUPPRESS_ALIASES[rid.lower()] == rid


def test_baseline_roundtrip_filters_findings(tmp_path):
    scratch = tmp_path / "seeded.py"
    scratch.write_text(
        "import jax.numpy as jnp\n\n"
        "def total(bits):\n"
        "    return jnp.sum(bits.astype(jnp.int32))\n"
    )
    dicts = run_lint([str(scratch)], jaxpr=False, repo_root=str(tmp_path))
    assert [d["rule"] for d in dicts] == ["G001"]

    bl = tmp_path / "baseline.json"
    baseline_mod.write(str(bl), dicts)
    grandfathered = baseline_mod.load(str(bl))
    assert {d["fingerprint"] for d in dicts} == grandfathered

    # a baselined finding no longer gates; a new one still does
    scratch.write_text(
        scratch.read_text()
        + "\ndef sync(bits):\n    return int(jnp.max(bits, axis=0))\n"
    )
    dicts2 = run_lint([str(scratch)], jaxpr=False, repo_root=str(tmp_path))
    fresh = [d for d in dicts2 if d["fingerprint"] not in grandfathered]
    assert [d["rule"] for d in fresh] == ["G002"]


def test_fingerprint_survives_line_moves(tmp_path):
    a = tmp_path / "mod.py"
    a.write_text("import jax.numpy as jnp\n\n"
                 "def f(b):\n    return jnp.sum(b.astype(jnp.int32))\n")
    d1 = run_lint([str(a)], jaxpr=False, repo_root=str(tmp_path))
    a.write_text("import jax.numpy as jnp\n\n\n\n# padding\n\n"
                 "def f(b):\n    return jnp.sum(b.astype(jnp.int32))\n")
    d2 = run_lint([str(a)], jaxpr=False, repo_root=str(tmp_path))
    assert d1[0]["fingerprint"] == d2[0]["fingerprint"]
    assert d1[0]["line"] != d2[0]["line"]


@pytest.mark.slow
def test_cli_module_clean_json():
    """`python -m tools.graftlint redisson_tpu/ --json` exits 0 with no
    findings — the exact CI gate invocation (both tiers)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "redisson_tpu", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["baselined"] == []


def test_cli_seeded_violations_gate(tmp_path):
    scratch = tmp_path / "viol.py"
    scratch.write_text(
        "import jax.numpy as jnp\n\n"
        "def bad_total(bits):\n"
        "    return jnp.sum(bits.astype(jnp.int32))\n\n"
        "def bad_sync(bits):\n"
        "    return int(jnp.max(bits, axis=0))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(scratch),
         "--json", "--no-jaxpr"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    got = {(d["rule"], d["line"]) for d in payload["findings"]}
    assert got == {("G001", 4), ("G002", 7)}


# ---------------------------------------------------------------------------
# 4. Tier B checker unit tests (on synthetic jaxprs, not the repo registry)
# ---------------------------------------------------------------------------

def test_j001_flags_x64_leak():
    import jax
    import jax.numpy as jnp

    from tools.graftlint.jaxpr_audit import _check_one

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x.astype(jnp.int64) + 1)(
            jnp.zeros((4,), jnp.int32))
    findings = _check_one("synthetic", closed, {})
    assert "J001" in {f.rule for f in findings}


def test_j002_flags_narrowing_after_reduction():
    import jax
    import jax.numpy as jnp

    from tools.graftlint.jaxpr_audit import _check_one

    def narrow(x):
        return jnp.sum(x.astype(jnp.uint32).reshape(2, 8),
                       axis=1).astype(jnp.uint8)

    closed = jax.make_jaxpr(narrow)(jnp.zeros((16,), jnp.uint8))
    findings = _check_one("synthetic", closed, {})
    assert "J002" in {f.rule for f in findings}
    # a registered allow_narrow bound silences exactly that dtype
    assert _check_one("synthetic", closed,
                      {"uint8": "sum of 8 values <= 255"}) == []


def test_j002_widening_is_fine():
    import jax
    import jax.numpy as jnp

    from tools.graftlint.jaxpr_audit import _check_one

    def widen(x):
        return jnp.sum(x.astype(jnp.int32).reshape(2, 8), axis=1)

    closed = jax.make_jaxpr(widen)(jnp.zeros((16,), jnp.uint8))
    assert _check_one("synthetic", closed, {}) == []


# ---------------------------------------------------------------------------
# 5. Tier C: concurrency discipline (G011-G014)
# ---------------------------------------------------------------------------

from tools.graftlint.concurrency import (ConcurrencyLinter,  # noqa: E402
                                         analyze_paths)


def clint_src(src, filename="scratch.py"):
    """Tier C lint of an in-memory source (explicit scope: always scanned)."""
    return ConcurrencyLinter(filename, repo_root=None, explicit=True,
                             source=textwrap.dedent(src)).run()


def test_repo_tier_c_clean():
    findings, _linters, graph = analyze_paths([ENGINE_DIR], repo_root=REPO)
    assert findings == [], (
        "graftlint Tier C findings in redisson_tpu/ — fix, register the "
        "discipline in GUARDED_BY, or suppress with a reasoned "
        "`# graftlint: allow-<rule>(why)`:\n"
        + "\n".join(f"{f.file}:{f.line} {f.rule} {f.message}"
                    for f in findings)
    )
    assert graph["cycles"] == [], graph["cycles"]


def test_g011_unlocked_access_to_registered_attr():
    findings = clint_src("""
        import threading

        GUARDED_BY = {"Box.items": "_lock"}

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def bad_add(self, x):
                self.items.append(x)

            def good_add(self, x):
                with self._lock:
                    self.items.append(x)
    """)
    assert rules_of(findings) == ["G011"]
    assert len(findings) == 1
    assert "Box.items" in findings[0].message


def test_tier_c_wire_window_discipline_seeded():
    """The wire reply window's GUARDED_BY contract is enforceable: dropping
    the lock around the slots deque is a G011 — the same table
    serve/windows.py registers for the real ConnectionWindow."""
    findings = clint_src("""
        import threading

        GUARDED_BY = {"ConnectionWindow._slots": "_lock"}

        class ConnectionWindow:
            def __init__(self):
                self._lock = threading.Lock()
                self._slots = []

            def drain(self):
                out = list(self._slots)
                return out

            def complete(self, data):
                with self._lock:
                    self._slots.append(data)
    """)
    assert "G011" in rules_of(findings)


def test_tier_c_wire_files_in_scope():
    """serve/windows.py and wire/server.py must stay under Tier C analysis
    (they import the concurrency seam / threading) — a refactor that drops
    them out of scope silently un-checks the wire tier's shared state."""
    import ast as _ast
    for rel in (os.path.join("redisson_tpu", "serve", "windows.py"),
                os.path.join("redisson_tpu", "wire", "server.py")):
        path = os.path.join(REPO, rel)
        linter = ConcurrencyLinter(path, repo_root=REPO, explicit=False)
        with open(path) as f:
            tree = _ast.parse(f.read())
        assert linter.in_scope(tree), rel


def test_tier_c_geo_files_in_scope():
    """The geo applier/link/manager mutate shared LWW maps and link
    tables from journal-listener, link, and anti-entropy threads — all
    three files must stay under Tier C analysis."""
    import ast as _ast
    for rel in (os.path.join("redisson_tpu", "geo", "applier.py"),
                os.path.join("redisson_tpu", "geo", "link.py"),
                os.path.join("redisson_tpu", "geo", "manager.py")):
        path = os.path.join(REPO, rel)
        linter = ConcurrencyLinter(path, repo_root=REPO, explicit=False)
        with open(path) as f:
            tree = _ast.parse(f.read())
        assert linter.in_scope(tree), rel


def test_tier_c_geo_applier_discipline_seeded():
    """The geo applier's GUARDED_BY contract is enforceable: touching the
    version vector without the lock is a G011 — the same table
    geo/applier.py registers for the real GeoApplier."""
    findings = clint_src("""
        import threading

        GUARDED_BY = {"GeoApplier.vv": "_lock"}

        class GeoApplier:
            def __init__(self):
                self._lock = threading.Lock()
                self.vv = {}

            def bad_watermark(self, origin, seq):
                self.vv[origin] = seq

            def good_watermark(self, origin, seq):
                with self._lock:
                    self.vv[origin] = seq
    """)
    assert rules_of(findings) == ["G011"]
    assert "GeoApplier.vv" in findings[0].message


def test_g011_locked_suffix_convention():
    # *_locked methods are analyzed as if the caller already holds every
    # convention lock of the class — no finding inside them.
    findings = clint_src("""
        import threading

        GUARDED_BY = {"Box.items": "_lock"}

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def _add_locked(self, x):
                self.items.append(x)

            def add(self, x):
                with self._lock:
                    self._add_locked(x)
    """)
    assert findings == []


def test_g011_inline_guarded_by_comment():
    findings = clint_src("""
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []  # guarded-by: _lock

            def bad(self):
                self.items.append(1)
    """)
    assert rules_of(findings) == ["G011"]


def test_g011_writes_mode_exempts_reads():
    findings = clint_src("""
        import threading

        GUARDED_BY = {"Box.flag": "_lock:writes"}

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.flag = False

            def peek(self):
                return self.flag  # unlocked read: fine under :writes

            def trip(self):
                self.flag = True  # unlocked write: flagged
    """)
    assert rules_of(findings) == ["G011"]
    assert len(findings) == 1


def test_g011_thread_and_racy_modes_exempt():
    findings = clint_src("""
        import threading

        GUARDED_BY = {
            "Box.a": "thread:loop-confined, mutated only pre-start",
            "Box.b": "racy:diagnostics string, stale reads fine",
        }

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.a = 0
                self.b = ""

            def loop(self):
                self.a += 1
                self.b = "x"
    """)
    assert findings == []


def test_g012_two_roots_no_lock():
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                self.count += 1

            def bump(self):
                self.count += 1
    """)
    assert rules_of(findings) == ["G012"]
    assert "Svc.count" in findings[0].message


def test_g012_common_lock_is_clean():
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                with self._lock:
                    self.count += 1

            def bump(self):
                with self._lock:
                    self.count += 1
    """)
    assert findings == []


def test_g012_registered_discipline_is_clean():
    findings = clint_src("""
        import threading

        GUARDED_BY = {"Svc.count": "thread:loop and bump never overlap"}

        class Svc:
            def __init__(self):
                self.count = 0
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                self.count += 1

            def bump(self):
                self.count += 1
    """)
    assert findings == []


def test_g012_callback_arg_is_a_root():
    # a bound method handed to another object as a callback is a thread
    # entry root even without a Thread(...) constructor.
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self, bus):
                self._lock = threading.Lock()
                self.seen = 0
                bus.subscribe(self._on_event)

            def _on_event(self, ev):
                self.seen += 1

            def poll(self):
                self.seen += 1
    """)
    assert rules_of(findings) == ["G012"]


def test_g013_future_result_under_lock():
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self, fut):
                with self._lock:
                    return fut.result()

            def good(self, fut):
                res = fut.result()
                with self._lock:
                    return res
    """)
    assert rules_of(findings) == ["G013"]
    assert len(findings) == 1


def test_g013_event_wait_under_lock():
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._ev = threading.Event()

            def bad(self):
                with self._lock:
                    self._ev.wait()
    """)
    assert rules_of(findings) == ["G013"]


def test_g013_condition_wait_is_exempt():
    # Condition.wait releases the lock it wraps — not a hold-and-block.
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)

            def park(self):
                with self._cv:
                    self._cv.wait(timeout=1.0)
    """)
    assert findings == []


def test_g013_queue_get_under_lock():
    findings = clint_src("""
        import queue
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def bad(self):
                with self._lock:
                    return self._q.get()
    """)
    assert rules_of(findings) == ["G013"]


def test_g013_one_hop_through_private_method():
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def _drain(self, fut):
                return fut.result()

            def bad(self, fut):
                with self._lock:
                    return self._drain(fut)
    """)
    assert "G013" in rules_of(findings)


def test_g013_suppression_with_reason():
    findings = clint_src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def serialized(self, fut):
                with self._lock:
                    # graftlint: allow-hold(serialization is the design; nothing else takes _lock)
                    return fut.result()
    """)
    assert findings == []


def test_g014_two_lock_inversion(tmp_path):
    mod = tmp_path / "tangle.py"
    mod.write_text(textwrap.dedent("""
        import threading

        class Tangle:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:
                        pass

            def backward(self):
                with self._b:
                    with self._a:
                        pass
    """))
    findings, _linters, graph = analyze_paths([str(mod)], repo_root=None)
    assert "G014" in {f.rule for f in findings}
    assert len(graph["cycles"]) == 1
    nodes = set(graph["cycles"][0]["nodes"])
    assert {"tangle.Tangle._a", "tangle.Tangle._b"} <= nodes
    # consistent ordering in a second module must NOT cycle
    ok = tmp_path / "ordered.py"
    ok.write_text(textwrap.dedent("""
        import threading

        class Ordered:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._a:
                    with self._b:
                        pass
    """))
    findings, _linters, graph = analyze_paths([str(ok)], repo_root=None)
    assert findings == []
    assert graph["edges"] and graph["cycles"] == []


def test_g014_one_hop_edge(tmp_path):
    # lock held across a self-call whose body takes another lock still
    # contributes an order edge.
    mod = tmp_path / "hop.py"
    mod.write_text(textwrap.dedent("""
        import threading

        class Hop:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def _inner(self):
                with self._b:
                    pass

            def outer(self):
                with self._a:
                    self._inner()

            def backward(self):
                with self._b:
                    with self._a:
                        pass
    """))
    findings, _linters, graph = analyze_paths([str(mod)], repo_root=None)
    assert "G014" in {f.rule for f in findings}


def test_tier_c_suppression_requires_reason():
    src = """
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                self.count += 1  # graftlint: allow-shared()

            def bump(self):
                self.count += 1
    """
    findings = clint_src(src)
    assert rules_of(findings) == ["G012"], "empty reason must not suppress"
    findings = clint_src(src.replace(
        "allow-shared()", "allow-shared(loop and bump never overlap)"))
    assert findings == []


def test_tier_c_rules_registered():
    for rule in ("G011", "G012", "G013", "G014"):
        assert rule in RULES
    for alias in ("guarded", "shared", "hold", "lockcycle"):
        assert alias in SUPPRESS_ALIASES


def test_tier_c_findings_are_baselinable():
    # Tier C findings carry the same fingerprint scheme as Tier A, so the
    # --baseline machinery covers them uniformly.
    from tools.graftlint.cli import collect_full

    src = textwrap.dedent("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def bad(self, fut):
                with self._lock:
                    return fut.result(timeout=5)
    """)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "svc.py")
        with open(p, "w") as fh:
            fh.write(src)
        dicts, tier_c = collect_full([p], jaxpr=False, repo_root=td)
        assert [d["rule"] for d in dicts] == ["G013"]
        assert dicts[0]["fingerprint"]
        assert tier_c["rules"]["G013"] == 1
        bl = os.path.join(td, "bl.json")
        baseline_mod.write(bl, dicts)
        grandfathered = baseline_mod.load(bl)
        assert dicts[0]["fingerprint"] in grandfathered


def test_cli_json_tier_c_block():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json", "--no-jaxpr",
         os.path.join(ENGINE_DIR, "persist", "journal.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] == []
    assert set(payload["tier_c"]["rules"]) == {"G011", "G012", "G013", "G014"}
    assert "edges" in payload["tier_c"]["lock_graph"]
    assert "cycles" in payload["tier_c"]["lock_graph"]


def test_interop_is_out_of_tier_c_scope():
    # asyncio interop runs single-writer on the event loop — documented
    # exclusion, no thread-lock discipline to check.
    sub = os.path.join(ENGINE_DIR, "interop")
    if not os.path.isdir(sub):
        pytest.skip("no interop package")
    findings, _linters, _graph = analyze_paths([sub], repo_root=REPO)
    assert findings == []


# ---------------------------------------------------------------------------
# 5. Tier D: asyncio/event-loop discipline (asynclint)
# ---------------------------------------------------------------------------

from tools.graftlint.asynclint import (AsyncLinter,  # noqa: E402
                                       analyze_paths as analyze_async)
from tools.graftlint.findings import tier_of  # noqa: E402


def alint_src(src, filename="scratch.py"):
    """Tier D lint of an in-memory source (explicit scope: always scanned)."""
    return AsyncLinter(filename, repo_root=None, explicit=True,
                       source=textwrap.dedent(src)).run()


def test_repo_tier_d_clean():
    findings, _linters = analyze_async([ENGINE_DIR], repo_root=REPO)
    assert findings == [], (
        "graftlint Tier D findings in redisson_tpu/ — fix, declare the "
        "affinity in LOOP_CONFINED (lifecycle= for setup/teardown), or "
        "suppress with a reasoned `# graftlint: allow-<rule>(why)`:\n"
        + "\n".join(f"{f.file}:{f.line} {f.rule} {f.message}"
                    for f in findings)
    )


def test_tier_d_scans_wire_and_interop():
    # Tier D's implicit scope is exactly the event-loop packages; the Tier C
    # exclusion of interop/ is complemented here, not contradicted.
    for sub in ("wire", "interop"):
        d = os.path.join(ENGINE_DIR, sub)
        if not os.path.isdir(d):
            pytest.skip(f"no {sub} package")
        _findings, linters = analyze_async([d], repo_root=REPO)
        assert any(lt.scoped for lt in linters), f"{sub}/ not scanned"


def test_g015_blocking_call_in_coroutine():
    findings = alint_src("""
        import asyncio
        import time

        class Conn:
            async def handle(self):
                time.sleep(0.5)
    """)
    assert "G015" in rules_of(findings)


def test_g015_one_hop_through_private_sync_helper():
    findings = alint_src("""
        import asyncio
        import time

        class Conn:
            async def handle(self):
                self._drain()

            def _drain(self):
                time.sleep(0.1)
    """)
    assert "G015" in rules_of(findings)
    assert any("_drain" in f.message for f in findings)


def test_g015_await_and_executor_dispatch_exempt():
    findings = alint_src("""
        import asyncio
        import time

        class Conn:
            async def handle(self, loop):
                await asyncio.sleep(0.1)
                await loop.run_in_executor(None, self._fsync_all)
                await asyncio.to_thread(self._fsync_all)

            def _fsync_all(self):
                import os
                os.fsync(3)
    """)
    assert "G015" not in rules_of(findings)


def test_g015_lock_provenance_thread_vs_asyncio():
    # Only locks with threading provenance block the loop; an asyncio.Lock
    # acquire is loop-native and must not be flagged.
    findings = alint_src("""
        import asyncio
        import threading

        class Mixed:
            def __init__(self):
                self._alock = asyncio.Lock()
                self._tlock = threading.Lock()

            async def bad(self):
                self._tlock.acquire()

            async def fine(self):
                self._alock.acquire()
    """)
    g015 = [f for f in findings if f.rule == "G015"]
    assert len(g015) == 1
    assert "lock.acquire" in g015[0].message


def test_g016_discarded_coroutine():
    findings = alint_src("""
        import asyncio

        class Svc:
            async def _notify(self):
                pass

            def kick(self):
                self._notify()
    """)
    assert "G016" in rules_of(findings)


def test_g016_dropped_task_reference():
    findings = alint_src("""
        import asyncio

        async def work():
            pass

        class Svc:
            def kick(self):
                asyncio.ensure_future(work())
    """)
    assert "G016" in rules_of(findings)
    assert any("weak reference" in f.message for f in findings)


def test_g016_held_reference_pattern_clean():
    # The blessed idiom: keep a strong ref, discard on completion.
    findings = alint_src("""
        import asyncio

        class Svc:
            def __init__(self):
                self._tasks = set()

            async def _notify(self):
                pass

            def kick(self):
                t = asyncio.ensure_future(self._notify())
                self._tasks.add(t)
                t.add_done_callback(self._tasks.discard)
    """)
    assert "G016" not in rules_of(findings)


def test_g017_mutation_from_thread_root():
    findings = alint_src("""
        import asyncio
        import threading

        LOOP_CONFINED = {"Srv._conns": "connection registry; lifecycle=start"}

        class Srv:
            def __init__(self):
                self._conns = {}

            def start(self):
                self._conns = {}
                threading.Thread(target=self._bg, daemon=True).start()

            def _bg(self):
                self._conns["x"] = 1

            async def register(self, c):
                self._conns["c"] = c
    """)
    g017 = [f for f in findings if f.rule == "G017"]
    # only the thread-entry mutation fires: __init__ and lifecycle=start are
    # exempt, and the async method IS the loop.
    assert len(g017) == 1
    assert "_bg" in g017[0].message


def test_g017_mutation_from_done_callback_root():
    findings = alint_src("""
        import asyncio

        LOOP_CONFINED = {"Srv._pending": "in-flight ops; loop-owned"}

        class Srv:
            def __init__(self):
                self._pending = {}

            def submit(self, ex, op):
                f = ex.submit(op)
                f.add_done_callback(self._done)

            def _done(self, f):
                self._pending.pop(id(f), None)
    """)
    assert "G017" in rules_of(findings)


def test_g017_var_based_key_flags_cross_thread_facade():
    findings = alint_src("""
        import asyncio

        LOOP_CONFINED = {"_pool._listeners": "listener list; loop-owned"}

        class Facade:
            def add_listener(self, fn):
                self._pool._listeners.append(fn)

            def add_listener_ok(self, fn):
                self._loop.call_soon_threadsafe(
                    self._pool._listeners.append, fn)
    """)
    g017 = [f for f in findings if f.rule == "G017"]
    assert len(g017) == 1
    assert "add_listener" in g017[0].message


def test_g018_future_completion_from_done_callback():
    findings = alint_src("""
        import asyncio

        class Bridge:
            def submit(self, ex, fut, op):
                cf = ex.submit(op)
                cf.add_done_callback(self._done)
                self._fut = fut

            def _done(self, cf):
                self._fut.set_result(cf.result())
    """)
    assert "G018" in rules_of(findings)


def test_g018_marshalled_completion_clean():
    findings = alint_src("""
        import asyncio

        class Bridge:
            def submit(self, ex, fut, op):
                cf = ex.submit(op)
                cf.add_done_callback(self._done)
                self._fut = fut

            def _done(self, cf):
                self._loop.call_soon_threadsafe(
                    self._fut.set_result, cf.result())
    """)
    assert "G018" not in rules_of(findings)


def test_g018_asyncio_task_done_callback_is_loop_context():
    # add_done_callback on an asyncio Task runs ON the loop — completing a
    # future there is fine; only concurrent.futures callbacks are off-loop.
    findings = alint_src("""
        import asyncio

        class T:
            def start(self):
                self._t = asyncio.create_task(self._run())
                self._t.add_done_callback(self._finish)

            async def _run(self):
                pass

            def _finish(self, t):
                self._fut.set_result(1)
    """)
    assert "G018" not in rules_of(findings)


def test_tier_d_suppression_requires_reason():
    base = """
        import asyncio
        import time

        class Conn:
            async def handle(self):
                time.sleep(0.5){allow}
    """
    bare = alint_src(base.format(allow="  # graftlint: allow-loop"))
    assert "G015" in rules_of(bare)
    reasoned = alint_src(
        base.format(allow="  # graftlint: allow-loop(startup probe only)"))
    assert "G015" not in rules_of(reasoned)


def test_tier_d_rules_registered():
    for rule in ("G015", "G016", "G017", "G018"):
        assert rule in RULES
        assert tier_of(rule) == "d"
    for alias in ("loop", "unawaited", "affinity", "handoff"):
        assert alias in SUPPRESS_ALIASES
    assert tier_of("G011") == "c"
    assert tier_of("G002") == "a"
    assert tier_of("J001") == "b"


def test_tier_d_findings_are_baselinable():
    from tools.graftlint.cli import collect_tiers

    src = textwrap.dedent("""
        import asyncio
        import time

        class Conn:
            async def handle(self):
                time.sleep(0.5)
    """)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "conn.py")
        with open(p, "w") as fh:
            fh.write(src)
        dicts, tiers = collect_tiers([p], jaxpr=False, repo_root=td)
        assert [d["rule"] for d in dicts] == ["G015"]
        assert dicts[0]["fingerprint"]
        assert tiers["tier_d"]["rules"]["G015"] == 1
        assert tiers["tier_d"]["modules"] >= 1
        bl = os.path.join(td, "bl.json")
        baseline_mod.write(bl, dicts)
        assert dicts[0]["fingerprint"] in baseline_mod.load(bl)


def test_tier_scoped_baseline_update_preserves_other_tiers():
    # The satellite-6 pin: `--update-baseline --tier d` must not launder a
    # Tier A regression into the baseline, and must not drop entries the
    # other tiers already hold.
    from tools.graftlint.cli import collect_full

    a_src = textwrap.dedent("""
        import jax.numpy as jnp

        def count(bits):
            return int(jnp.sum(bits, axis=0)[0])
    """)
    d_src = textwrap.dedent("""
        import asyncio
        import time

        class Conn:
            async def handle(self):
                time.sleep(0.5)
    """)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        pa = os.path.join(td, "hot.py")
        pd = os.path.join(td, "conn.py")
        with open(pa, "w") as fh:
            fh.write(a_src)
        with open(pd, "w") as fh:
            fh.write(d_src)
        dicts, _ = collect_full([pa, pd], jaxpr=False, repo_root=td)
        by_rule = {d["rule"]: d for d in dicts}
        assert "G002" in by_rule and "G015" in by_rule
        bl = os.path.join(td, "bl.json")

        # A d-only update must NOT baseline the seeded G002.
        baseline_mod.write(bl, dicts, tiers=("d",))
        grand = baseline_mod.load(bl)
        assert by_rule["G015"]["fingerprint"] in grand
        assert by_rule["G002"]["fingerprint"] not in grand

        # And once tier A holds entries, a d-only rewrite keeps them.
        baseline_mod.write(bl, dicts)
        assert by_rule["G002"]["fingerprint"] in baseline_mod.load(bl)
        baseline_mod.write(bl, [by_rule["G015"]], tiers=("d",))
        grand2 = baseline_mod.load(bl)
        assert by_rule["G002"]["fingerprint"] in grand2
        assert by_rule["G015"]["fingerprint"] in grand2


def test_baseline_v1_flat_format_still_loads():
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        bl = os.path.join(td, "bl.json")
        with open(bl, "w") as fh:
            json.dump({"findings": [{"fingerprint": "abc123",
                                     "rule": "G002", "file": "x.py"}]}, fh)
        assert "abc123" in baseline_mod.load(bl)


def test_cli_json_tier_d_block():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json", "--no-jaxpr",
         os.path.join(ENGINE_DIR, "interop", "pool.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] == []
    assert set(payload["tier_d"]["rules"]) == {"G015", "G016", "G017", "G018"}
    assert payload["tier_d"]["modules"] >= 1
    assert payload["tier_d"]["async_defs"] >= 1
    assert payload["tier_d"]["confined_keys"] >= 1


def test_cli_no_async_skips_tier_d():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json", "--no-jaxpr",
         "--no-async", os.path.join(ENGINE_DIR, "interop", "pool.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["tier_d"]["modules"] == 0


# ---------------------------------------------------------------------------
# Tier E: whole-program op-contract analysis (G019-G022)
# ---------------------------------------------------------------------------

def _contract_universe():
    """A minimal self-consistent op universe: four kinds, every registry
    agreeing. Each seeded-violation test perturbs exactly one key."""
    from redisson_tpu.commands import _d

    ops = {d.kind: d for d in [
        _d("hll_add", "PFADD", True, "engine tpu"),
        _d("hll_count", "PFCOUNT", False, "engine tpu"),
        _d("delete", "DEL", True, "engine tpu"),
        _d("geo_merge", "-", True, "engine"),
    ]}
    return {
        "op_table": ops,
        "cluster_kinds": frozenset(),
        "semilattice_kinds": frozenset({"hll_add"}),
        "destructive_kinds": frozenset({"delete"}),
        "ship_kinds": frozenset({"hll_add", "delete"}),
        "coalesce_groups": {"hll_add": "delta"},
        "global_coalesce": frozenset(),
        "read_kinds": frozenset({"hll_count"}),
        "pinned_kinds": frozenset(),
        "lint_write_kinds": frozenset({"hll_add", "delete", "geo_merge"}),
        "both_kinds": frozenset({"delete"}),
        "foldable_kinds": frozenset({"hll_add"}),
        "wire_kinds": frozenset({"hll_add", "hll_count", "delete"}),
        "facade_kinds": {"hll_add": ("models/hll.py", 1),
                         "hll_count": ("models/hll.py", 2)},
        "engine_handlers": {"hll_add", "hll_count", "geo_merge"},
        "tpu_handlers": {"hll_add", "hll_count"},
        "applier_local_branches": {"delete", "flushall"},
        "applier_rebuild_branches": {"geo_merge"},
    }


def contract_findings(**perturb):
    from tools.graftlint.contracts import analyze

    u = _contract_universe()
    u.update(perturb)
    findings, _, stats = analyze(**u)
    return findings, stats


def test_contract_universe_is_clean():
    findings, stats = contract_findings()
    assert findings == [], [f.message for f in findings]
    assert stats["kinds"] == 4 and stats["write_kinds"] == 3


def test_g019_registry_kind_not_in_op_table():
    findings, stats = contract_findings(
        cluster_kinds=frozenset({"warp_flip"}))
    assert [f.rule for f in findings] == ["G019"]
    assert "warp_flip" in findings[0].message
    assert "CLUSTER_KINDS" in findings[0].message
    assert stats["rules"]["G019"] == 1


def test_g019_foldable_kind_missing_from_coalesce():
    findings, _ = contract_findings(coalesce_groups={})
    assert [f.rule for f in findings] == ["G019"]
    assert "hll_add" in findings[0].message
    assert "COALESCE_GROUPS" in findings[0].message


def test_g019_kind_classified_both_semilattice_and_destructive():
    findings, _ = contract_findings(
        destructive_kinds=frozenset({"delete", "hll_add"}),
        applier_local_branches={"delete", "hll_add"})
    assert [f.rule for f in findings] == ["G019"]
    assert "BOTH" in findings[0].message


def test_g019_shipped_kind_unclassified():
    findings, _ = contract_findings(
        ship_kinds=frozenset({"hll_add", "delete", "hll_count"}))
    rules = [f.rule for f in findings]
    assert set(rules) == {"G019"}
    msgs = " | ".join(f.message for f in findings)
    assert "neither" in msgs          # unclassified
    assert "never journals" in msgs   # hll_count is not write=True


def test_g019_geo_record_kind_in_ship_set():
    findings, _ = contract_findings(
        ship_kinds=frozenset({"hll_add", "delete", "geo_merge"}),
        semilattice_kinds=frozenset({"hll_add", "geo_merge"}))
    assert any("echo-loop" in f.message for f in findings)
    assert all(f.rule == "G019" for f in findings)


def test_g019_g007_write_set_drift():
    findings, _ = contract_findings(
        lint_write_kinds=frozenset({"hll_add", "delete"}))  # geo_merge lost
    assert [f.rule for f in findings] == ["G019"]
    assert "G007" in findings[0].message
    assert findings[0].file == "tools/graftlint/astlint.py"


def test_g020_facade_kind_not_in_op_table():
    findings, _ = contract_findings(
        facade_kinds={"hll_add": ("models/hll.py", 1),
                      "mystery_op": ("models/hll.py", 9)})
    assert [f.rule for f in findings] == ["G020"]
    assert "mystery_op" in findings[0].message
    assert findings[0].file == "models/hll.py"
    assert findings[0].line == 9


def test_g020_facade_read_kind_unroutable():
    findings, _ = contract_findings(read_kinds=frozenset())
    assert [f.rule for f in findings] == ["G020"]
    assert "hll_count" in findings[0].message
    assert "READ_KINDS" in findings[0].message


def test_g020_wire_hole_without_contract_escape():
    findings, _ = contract_findings(wire_kinds=frozenset())
    assert {f.rule for f in findings} == {"G020"}
    flagged = {f.message.split("'")[1] for f in findings}
    assert flagged == {"hll_add", "hll_count", "delete"}


def test_g020_contract_escape_clears_wire_hole():
    from redisson_tpu.commands import _d

    u = _contract_universe()
    ops = dict(u["op_table"])
    ops["delete"] = _d("delete", "DEL", True, "engine tpu",
                       "engine-only(facade composite; router owns DEL)")
    findings, _ = contract_findings(
        op_table=ops, wire_kinds=frozenset({"hll_add", "hll_count"}))
    assert findings == [], [f.message for f in findings]
    # ... but an EMPTY reason is not an escape
    ops["delete"] = _d("delete", "DEL", True, "engine tpu", "engine-only( )")
    findings, _ = contract_findings(
        op_table=ops, wire_kinds=frozenset({"hll_add", "hll_count"}))
    assert [f.rule for f in findings] == ["G020"]


def test_g021_journaled_kind_without_replay_handler():
    findings, _ = contract_findings(
        tpu_handlers=frozenset({"hll_count"}))  # hll_add lost its handler
    assert [f.rule for f in findings] == ["G021"]
    assert "hll_add" in findings[0].message
    assert "tpu backend" in findings[0].message


def test_g021_both_kinds_satisfy_dispatch():
    # delete has NO _op_delete in either backend in the fixture — the
    # RoutingBackend._BOTH fan-out is its dispatch path, and that counts.
    findings, _ = contract_findings(both_kinds=frozenset())
    assert [f.rule for f in findings] == ["G021"]
    assert "delete" in findings[0].message


def test_g022_destructive_kind_missing_lww_branch():
    findings, _ = contract_findings(applier_local_branches={"flushall"})
    assert [f.rule for f in findings] == ["G022"]
    assert "delete" in findings[0].message
    assert "note_local" in findings[0].message


def test_g022_geo_kind_missing_rebuild_branch():
    findings, _ = contract_findings(applier_rebuild_branches=set())
    assert [f.rule for f in findings] == ["G022"]
    assert "geo_merge" in findings[0].message
    assert "rebuild" in findings[0].message


def test_tier_e_suppression_requires_reason():
    from tools.graftlint import contracts

    base = 'OP_TABLE = [\n    _d("delete", "DEL", True, "engine tpu"),{allow}\n]\n'
    rel = contracts.OP_TABLE_FILE

    def run(allow):
        src = contracts._Src(rel, base.format(allow=allow))
        findings, _ = contract_findings(
            wire_kinds=frozenset({"hll_add", "hll_count"}),
            sources={rel: src})
        return findings

    assert [f.rule for f in run("")] == ["G020"]
    # bare allow (no reason) does not suppress
    assert [f.rule for f in run("  # graftlint: allow-contract")] == ["G020"]
    assert [f.rule for f in run("  # graftlint: allow-contract()")] == ["G020"]
    # tier-wide escape with a reason does
    assert run("  # graftlint: allow-contract(router owns DEL)") == []
    # ... as does the per-rule alias and the rule id
    assert run("  # graftlint: allow-hole(router owns DEL)") == []
    assert run("  # graftlint: allow-g020(router owns DEL)") == []
    # a DIFFERENT rule's alias does not
    assert [f.rule for f in
            run("  # graftlint: allow-drift(router owns DEL)")] == ["G020"]


def test_tier_e_rules_registered():
    for rule in ("G019", "G020", "G021", "G022"):
        assert rule in RULES
        assert tier_of(rule) == "e"
    for alias in ("drift", "hole", "replay", "arbiter"):
        assert alias in SUPPRESS_ALIASES
    assert tier_of("G018") == "d"


def test_tier_e_findings_are_baselinable():
    from tools.graftlint import contracts

    findings, sources, _ = contracts.analyze(
        **{**_contract_universe(),
           "cluster_kinds": frozenset({"warp_flip"})})
    assert [f.rule for f in findings] == ["G019"]
    lines = sources.get(findings[0].file, [])
    text = lines[findings[0].line - 1] if findings[0].line <= len(lines) else ""
    d = findings[0].to_dict(text)
    assert d["fingerprint"]
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        bl = os.path.join(td, "bl.json")
        baseline_mod.write(bl, [d])
        assert d["fingerprint"] in baseline_mod.load(bl)
        with open(bl) as fh:
            data = json.load(fh)
        assert data["version"] == 3
        assert [e["fingerprint"] for e in data["tiers"]["e"]] == \
            [d["fingerprint"]]


def test_seeded_g002_survives_e_only_baseline_update():
    # The satellite-3 pin: `--update-baseline --tier e` must not launder a
    # Tier A regression into the baseline, and v1/v2 files still load.
    from tools.graftlint.cli import collect_full

    a_src = textwrap.dedent("""
        import jax.numpy as jnp

        def count(bits):
            return int(jnp.sum(bits, axis=0)[0])
    """)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        pa = os.path.join(td, "hot.py")
        with open(pa, "w") as fh:
            fh.write(a_src)
        dicts, _ = collect_full([pa], jaxpr=False, repo_root=td)
        by_rule = {d["rule"]: d for d in dicts}
        assert "G002" in by_rule
        e_dict = {"rule": "G019", "file": "redisson_tpu/commands.py",
                  "line": 1, "message": "seeded", "hint": "",
                  "fingerprint": "feedc0de00000000"}
        bl = os.path.join(td, "bl.json")

        # An e-only update must NOT baseline the seeded G002 ...
        baseline_mod.write(bl, dicts + [e_dict], tiers=("e",))
        grand = baseline_mod.load(bl)
        assert e_dict["fingerprint"] in grand
        assert by_rule["G002"]["fingerprint"] not in grand

        # ... and once tier A holds entries, an e-only rewrite keeps them.
        baseline_mod.write(bl, dicts)
        assert by_rule["G002"]["fingerprint"] in baseline_mod.load(bl)
        baseline_mod.write(bl, [e_dict], tiers=("e",))
        grand2 = baseline_mod.load(bl)
        assert by_rule["G002"]["fingerprint"] in grand2
        assert e_dict["fingerprint"] in grand2


def test_baseline_v2_format_still_loads():
    # A pre-Tier-E baseline (version 2, no "e" section) must keep loading.
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        bl = os.path.join(td, "bl.json")
        with open(bl, "w") as fh:
            json.dump({"version": 2,
                       "tiers": {"a": [{"fingerprint": "aaa111"}],
                                 "d": [{"fingerprint": "ddd444"}]}}, fh)
        grand = baseline_mod.load(bl)
        assert {"aaa111", "ddd444"} <= grand


def test_repo_tier_e_clean():
    from tools.graftlint.contracts import analyze

    findings, _, stats = analyze()
    assert findings == [], (
        "graftlint Tier E findings — the op contract drifted; fix the "
        "registry or declare a reasoned escape:\n"
        + "\n".join(f"{f.file}:{f.line} {f.rule} {f.message}"
                    for f in findings)
    )
    assert stats["kinds"] > 100
    assert stats["surfaces"]["wire"] >= 14
    assert stats["declared_cells"] >= 14


def test_tier_e_covers_live_registries():
    # The default gather() must see the real registries, not stand-ins.
    from redisson_tpu.cluster.shard import CLUSTER_KINDS
    from redisson_tpu.geo.applier import SHIP_KINDS
    from tools.graftlint.contracts import gather

    u = gather()
    assert u["cluster_kinds"] == CLUSTER_KINDS
    assert u["ship_kinds"] == SHIP_KINDS
    assert "hll_add" in u["wire_kinds"]        # wire AST extraction
    assert "bitset_clear" in u["wire_kinds"]   # incl. conditional-kind SETBIT
    assert "hll_add" in u["facade_kinds"]      # facade AST extraction
    assert "delete" in u["applier_local_branches"]
    assert "geo_merge" in u["applier_rebuild_branches"]
    assert "hll_add" in u["foldable_kinds"]


def test_cli_json_tier_e_block():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json", "--no-jaxpr",
         os.path.join(ENGINE_DIR, "interop", "pool.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] == []
    assert set(payload["tier_e"]["rules"]) == {"G019", "G020", "G021", "G022"}
    assert all(v == 0 for v in payload["tier_e"]["rules"].values())
    assert payload["tier_e"]["kinds"] > 100
    assert payload["tier_e"]["declared_cells"] >= 14


def test_cli_no_contracts_skips_tier_e():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json", "--no-jaxpr",
         "--no-contracts", os.path.join(ENGINE_DIR, "interop", "pool.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["tier_e"]["kinds"] == 0
    assert payload["tier_e"]["declared_cells"] == 0
