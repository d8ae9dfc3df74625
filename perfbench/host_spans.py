#!/usr/bin/env python3
"""Put the device's idle time down to the program's named host stages.

The program opens a `jax.profiler.TraceAnnotation` named
`rtpu/<stage>` around each host stage of its served path
(redisson_tpu/trace/stages.py). In the `.xplane.pb` those spans sit on
the `/host:CPU` line of the thread that ran them, beside the Python
tracer's frames, and that line is named after the thread: `python3` when
the process was started as `python3`. `trace_reduce.load` keeps only
lines named `python`, so under `python3 perfbench/run.py` it reads no
host event and every idle gap reads "host waiting".

Here `load` keeps every host line that holds a Python frame or a program
span, and `reduce` names each idle gap by the innermost program span
open at its midpoint on any thread, falling back to trace_reduce's
Python-frame cause where no span is open, and to "host waiting" where
neither is. On a trace with no program span its output equals
trace_reduce.reduce's. It adds:

  idle_by_span        idle seconds per program span (the gaps a span
                      names), inside `breakdown` as well
  flushed_ops_per_s   commands the wire flushed in the traced window
                      (the `ops` ids of its `rtpu/wire.flush` spans) per
                      second: the traced run's rate, for its on-cost
  spans_per_s         program spans per second, by name: with the rate
                      above, the spans a command costs

Run a cell with it in place of trace_reduce's reduction (same arguments
as perfbench/run.py; the result line is run.py's):

    python3 perfbench/host_spans.py --workload <cell> --seed <n> \
        --seconds <s> --trace 1
"""

from __future__ import annotations

import heapq
import os
import sys
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import trace_reduce  # noqa: E402

PREFIX = "rtpu/"  # redisson_tpu.trace.stages.PREFIX


def is_span(name: str) -> bool:
    return name.startswith(PREFIX)


def host_events(path: str):
    """([thread, name, start_ns, duration_ns] of every event on each
    `/host:CPU` line that holds a Python frame or a program span,
    {"flushed_ops": the summed `ops` ids of the `rtpu/wire.flush` spans,
    "spans": {span name: count}})."""
    from jax.profiler import ProfileData

    host, flushed, spans = [], 0, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for t, line in enumerate(plane.lines):
            events = list(line.events)
            if not any(e.name.startswith("$") or is_span(e.name)
                       for e in events):
                continue
            for e in events:
                host.append([t, e.name, e.start_ns, e.duration_ns])
                if is_span(e.name):
                    spans[e.name] = spans.get(e.name, 0) + 1
                if e.name == PREFIX + "wire.flush":
                    flushed += int(dict(e.stats).get("ops", 0))
    return host, {"flushed_ops": flushed, "spans": spans}


def load(path: str, device_ids: List[int]) -> Dict:
    """trace_reduce.load's compact form with `host` and `span_stats` from
    host_events."""
    tr = trace_reduce.load(path, device_ids)
    tr["host"], tr["span_stats"] = host_events(path)
    return tr


def _span_causes(host, mids: List[float]) -> List[str]:
    """The innermost (shortest) program span open at each instant of
    `mids` on any thread, or None where none is open."""
    spans = sorted((s, s + d, name) for _, name, s, d in host
                   if is_span(name))
    order = sorted(range(len(mids)), key=lambda i: mids[i])
    out: List[str] = [None] * len(mids)
    active: List[tuple] = []  # heap of (end, start, name)
    k = 0
    for i in order:
        t = mids[i]
        while k < len(spans) and spans[k][0] <= t:
            heapq.heappush(active, (spans[k][1], spans[k][0], spans[k][2]))
            k += 1
        while active and active[0][0] <= t:
            heapq.heappop(active)
        if active:
            out[i] = min(active, key=lambda e: e[0] - e[1])[2]
    return out


def idle_gaps(tr: Dict) -> List[tuple]:
    """[(length_ns, start_ns)] of device idle in the window, on the lowest
    device id (trace_reduce.reduce's gaps)."""
    w0, w1 = tr["window_ns"]
    dev = tr["devices"][min(tr["devices"])]
    merged = trace_reduce._union(
        [[max(s, w0), min(s + d, w1)] for _, s, d in dev["ops"]
         if s < w1 and s + d > w0])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(e - s, s) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def reduce(tr: Dict, kernels: Dict[str, object]) -> Dict:
    """trace_reduce.reduce, with idle gaps named by program span first."""
    red = trace_reduce.reduce(tr, kernels)
    gaps = idle_gaps(tr)
    mids = [s + n / 2 for n, s in gaps]
    frames = [h for h in tr["host"] if not is_span(h[1])]
    causes = [span or frame for span, frame in zip(
        _span_causes(tr["host"], mids),
        trace_reduce._host_causes(frames, mids))]
    idle: Dict[str, float] = {}
    for (length, _), cause in zip(gaps, causes):
        idle[cause] = idle.get(cause, 0.0) + length / 1e9
    idle = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    red["idle_by_cause"] = idle
    red["idle_by_span"] = {k: v for k, v in idle.items() if is_span(k)}
    red["breakdown"]["idle_gaps"] = [[k, v] for k, v in
                                     list(idle.items())[:10]]
    if red["idle_by_span"]:
        red["breakdown"]["idle_by_span"] = red["idle_by_span"]
    if "span_stats" in tr:
        w = red["window_s"]
        red["breakdown"]["flushed_ops_per_s"] = \
            tr["span_stats"]["flushed_ops"] / w
        red["breakdown"]["spans_per_s"] = {
            k: n / w for k, n in sorted(tr["span_stats"]["spans"].items())}
    return red


def reduce_dir(tracedir: str, device_ids: List[int], root: str) -> Dict:
    return reduce(load(trace_reduce.find_xplane(tracedir), device_ids),
                  trace_reduce.kernel_modules(root))


def main(argv=None) -> int:
    from perfbench import run

    # run.py reduces its trace through trace_reduce.reduce_dir
    trace_reduce.reduce_dir = reduce_dir
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
