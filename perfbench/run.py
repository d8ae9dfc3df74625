#!/usr/bin/env python3
"""Run one cell of the benchmark once on the chip:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It starts the program from the cell's configuration, fills its state, starts
the load generator children (writers in a closed loop, readers in an open
loop, over RESP on loopback), warms up under that same load, measures for
`--seconds`, checks what the program answered against the plain reference,
and prints one JSON object as the last line of stdout. With `--trace 0` the
metrics are the cell's end-to-end metrics; with `--trace 1` one profiler
trace of a few seconds is taken inside the window and the metrics are the
cell's per-layer metrics. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result; `JAX_PLATFORMS=cpu`
set explicitly makes it a rehearsal on the CPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import struct  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import check, spec, traffic  # noqa: E402

TRACE_LEAD_S = 1.0  # the trace starts this long into the window
TRACE_S = 3.0       # and lasts this long
DRAIN_S = 60.0      # replies due after the window may take this long
SETTLE_S = 5.0      # the warm-up ends once no program was built this long
READ_BACKLOG_S = 1.0  # ... and at most this many seconds of reads queue
WARMUP_MAX_S = 180.0  # past this the window starts anyway (a run ends in 360 s)


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Devices and compilation


def devices_for(chips: int):
    """The first `chips` devices; a TPU unless JAX_PLATFORMS=cpu was set."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise NoDevice(f"no TPU: JAX reports {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX reports "
                       f"{len(devs)}")
    return devs[:chips]


def use_compile_cache(root: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment names, so only a checkout's first run of a
    cell compiles (the program keeps a cache dir already configured)."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)  # JAX does not make it
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class Compiles:
    """Programs built (compiled, or loaded from the persistent cache),
    stamped on the monotonic clock with their names."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.at = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.at.append((time.monotonic(), kwargs.get("fun_name", "?"),
                            duration))

    def between(self, t0: float, t1: float):
        return [(name, d) for t, name, d in self.at if t0 <= t < t1]


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------------------
# Load generator children


class Child:
    def __init__(self, role: traffic.Role, host: str, port: int, seed: int,
                 cluster: bool):
        self.role = role
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=ROOT)
        job = {"role": role.name, "spec": role.spec, "space": role.space,
               "seed": seed, "host": host, "port": port, "cluster": cluster}
        self._send(job)

    def _send(self, obj) -> None:
        self.proc.stdin.write((json.dumps(obj) + "\n").encode())
        self.proc.stdin.flush()

    def ready(self) -> None:
        line = self.proc.stdout.readline()
        if line.strip() != b"ready":
            raise RuntimeError(f"{self.role.name} child did not start")

    def go(self, clock) -> None:
        self._send(clock)

    def result(self, timeout: float):
        n = self.proc.stdout.read(8)
        if len(n) != 8:
            self.proc.wait(timeout)
            raise RuntimeError(f"{self.role.name} child failed "
                               f"(exit {self.proc.returncode})")
        blob = self.proc.stdout.read(struct.unpack("<Q", n)[0])
        self.proc.wait(timeout)
        import io

        z = np.load(io.BytesIO(blob))
        rec = {k: z[k] for k in z.files if k != "info"}
        return rec, json.loads(str(z["info"]))

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def _sleep_until(t: float) -> None:
    while (left := t - time.monotonic()) > 0:
        time.sleep(min(left, 0.1))


def _p(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q, method="inverted_cdf"))


# ---------------------------------------------------------------------------
# One run


def warm_up(cell: spec.Cell, system, compiles: "Compiles",
            t_start: float) -> float:
    """Run the cell's own load until it is steady, and return when the
    window starts: at least the traffic's `warmup_s`, then until no
    program was built for SETTLE_S and the wire holds no more commands in
    flight than the writers keep plus READ_BACKLOG_S of reads, so
    first-use compiles and the backlog they leave all fall in set-up.
    Past WARMUP_MAX_S the window starts anyway (logged)."""
    w, r = cell.traffic["writers"], cell.traffic["readers"]
    most = (int(w["connections"]) * int(w["pipeline"])
            + float(r["rate"]) * READ_BACKLOG_S)
    t_min = t_start + float(cell.traffic["warmup_s"])
    while True:
        _sleep_until(time.monotonic() + 0.25)
        now = time.monotonic()
        built = [t for t, _, _ in compiles.at]
        quiet = now - max([t_start] + built)
        inflight = system.counters()["wire"].get("inflight", 0)
        if now >= t_min and quiet >= SETTLE_S and inflight <= most:
            break
        if now - t_start > WARMUP_MAX_S:
            log(f"# warm-up not steady after {WARMUP_MAX_S} s: quiet "
                f"{quiet:.1f} s, {inflight} commands in flight")
            break
    log(f"# warm-up: {now - t_start:.3f} s, {len(built)} programs built")
    return now + 0.05


#: The control: the reference in the program's place, breaking the stated
#: guarantee that an acknowledged write is seen by every later command.
#: Reads see state 50 ms old, the read-back state that lost every 1000th
#: acknowledged write; each write command's model takes one of the two
#: for its replies (perfbench/state/<type>.py).
CONTROL = {"lag_s": 0.05, "drop_every": 1000}


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_process: float = None, patch=None, control: bool = False):
    """Run the cell once and return its result line as a dict.
    `patch(system)` breaks the program for the harness's own tests;
    `control` adds `control_checks`, the numbers compared when the
    control (CONTROL) answers in the program's place."""
    from perfbench.system import System, window_delta

    t_process = T_PROCESS if t_process is None else t_process
    devices = devices_for(cell.chips)
    if devices[0].platform == "tpu":
        use_compile_cache(ROOT)
    compiles = Compiles()
    system = System(cell.config, traced)
    children = []
    tracedir = None
    try:
        if patch is not None:
            patch(system)
        t = time.monotonic()
        system.fill(seed)
        log(f"# fill: {time.monotonic() - t:.3f} s, peak device bytes "
            f"{peak_bytes(devices)}")
        roles = {name: traffic.Role(name, cell.traffic[name], cell.space,
                                    seed)
                 for name in ("writers", "readers")}
        children = [Child(r, system.host, system.port, seed, system.cluster)
                    for r in roles.values()]
        for ch in children:
            ch.ready()
        t_start = time.monotonic() + 0.2
        for ch in children:
            ch.go({"t_start": t_start, "drain_s": DRAIN_S})
        t0 = warm_up(cell, system, compiles, t_start)
        t1 = t0 + seconds
        for ch in children:
            ch.go({"t0": t0, "t_end": t1})
        _sleep_until(t0)
        c0 = system.counters()
        log(f"# warm-up done, peak device bytes {peak_bytes(devices)}")
        spans0 = len(system.spans)
        trace = None
        if traced:
            import jax

            _sleep_until(t0 + TRACE_LEAD_S)
            tracedir = tempfile.mkdtemp(prefix="perfbench-trace-")
            tr0 = time.monotonic()
            jax.profiler.start_trace(tracedir)
            _sleep_until(tr0 + TRACE_S)
            jax.profiler.stop_trace()
            trace = (tracedir, tr0)
        _sleep_until(t1)
        c1 = system.counters()
        spans = system.spans[spans0:]
        built = compiles.between(t0, t1)
        log(f"# compiles inside the window: {len(built)} "
            f"{[(n, round(d, 3)) for n, d in built]}")
        recs = {}
        for ch in children:
            recs[ch.role.name] = ch.result(DRAIN_S + 30)
        peak = peak_bytes(devices)
        ck = check.Check(cell, seed, roles["writers"], roles["readers"],
                         recs["writers"][0], recs["readers"][0], (t0, t1))
        got = [system.command(a) for a in ck.readback_commands()]
        rule_inputs = system.rule_inputs()
    finally:
        for ch in children:
            ch.stop()
        system.close()
    # the program's state is freed; the reference runs on the host
    wrec, winfo = recs["writers"]
    rrec, rinfo = recs["readers"]
    log(f"# writers: {winfo}; readers: {rinfo}")
    log(f"# ingest rule inputs: {json.dumps(rule_inputs)}")
    checks = compare(cell, ck, got, winfo, rinfo)
    log(f"# worst read (gap, command, answer, low, high): {ck.worst}")

    # every write outstanding at some instant of the window
    in_w = (wrec["t_send"] < t1) & (wrec["t_reply"] >= t0)
    done_w = ((wrec["t_reply"] >= t0) & (wrec["t_reply"] < t1)
              & ~wrec["err"])
    in_r = (rrec["due"] >= t0) & (rrec["due"] < t1)
    attempted = int(in_w.sum() + in_r.sum() + winfo["unanswered"]
                    + rinfo["unanswered"])
    if winfo["errors"] or rinfo["errors"]:
        log(f"# error replies (whole run): writers {winfo['errors']}, "
            f"readers {rinfo['errors']}")
    failed = int(wrec["err"][in_w].sum() + rrec["err"][in_r].sum()
                 + winfo["unanswered"] + rinfo["unanswered"])
    late = rrec["t_send"][in_r] - rrec["due"][in_r]
    read_lat = rrec["t_reply"][in_r] - rrec["due"][in_r]
    log(f"# reader lateness: p50 {_p(late, 50) * 1e3:.3f} ms, p99 "
        f"{_p(late, 99) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms "
        f"over {len(late)} reads")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    # an error or nil reply in the window is a wrong answer (limit 0)
    checks["failed_replies"] = {"value": float(failed), "limit": 0.0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed}
    if not traced:
        measured = {
            "write_ops_per_s": (float(done_w.sum()) / seconds, "ops/s"),
            "write_p99_ms": (_p(wrec["t_reply"][in_w] - wrec["t_send"][in_w],
                                99) * 1e3, "ms"),
            "read_p99_ms": (_p(read_lat, 99) * 1e3, "ms"),
            "read_p95_ms": (_p(read_lat, 95) * 1e3, "ms"),
            "hbm_peak_gib": (peak / 2 ** 30, "GiB"),
            "setup_s": (t0 - t_process, "s"),
        }
        metrics = {m["name"]: {"value": measured[m["name"]][0],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        from perfbench import trace_reduce

        tracedir, tr0 = trace
        try:
            red = trace_reduce.reduce_dir(tracedir, [d.id for d in devices],
                                          ROOT)
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
        ctx = {"delta": window_delta(c0, c1), "seconds": seconds,
               "writes": int(done_w.sum()), "reads": int(in_r.sum()),
               "spans": spans, "trace": red, "late_s": late,
               "device_kind": devices[0].device_kind}
        metrics = {}
        for m in cell.per_layer:
            v = spec.load_module("layers", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log(f"# trace: {json.dumps(red['kernels'])}")
    result["metrics"] = metrics
    result["device"] = device
    if control:
        result["control_checks"] = compare(cell, ck, got, winfo, rinfo,
                                           CONTROL)
        # the reference in the program's place answers every command
        result["control_checks"]["failed_replies"] = {
            "value": 0.0, "limit": 0.0}
    result["checks"] = checks
    return result


def compare(cell, ck, got, winfo, rinfo, control=None):
    """`answer_gap`, with its limit from the traffic file: the widest
    relative gap of any answer checked (check.py's docstring), 1 for a
    write reply that no serial order gives or a command never answered.
    run_cell adds `failed_replies`."""
    control = control or {}
    wrong_share = f"{ck.writes.cmd.lower()}_wrong_share"
    parts = {
        "read": ck.read_gap(lag_s=control.get("lag_s", 0.0)),
        "read_back": ck.readback_gap(
            got, drop_every=control.get("drop_every", 0)),
        wrong_share: ck.write_wrong_share(control),
        "unanswered": winfo["unanswered"] + rinfo["unanswered"],
    }
    log(f"# answer gaps{' (control)' if control else ''}: {parts}")
    wrong_replies = parts[wrong_share] or parts["unanswered"]
    value = 1.0 if wrong_replies else max(parts["read"], parts["read_back"])
    return {"answer_gap": {"value": float(value),
                           "limit": float(cell.traffic["limits"]
                                          ["answer_gap"])}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "redisson_tpu")):
        log("perfbench: the program (redisson_tpu/) is not in this checkout")
        return 2
    try:
        cell = spec.load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoDevice as exc:
        log(f"perfbench: {exc}")
        return 1
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
