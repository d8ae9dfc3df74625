"""Named host stages on the profiler's clock.

Each host stage of the served path opens a ``jax.profiler.TraceAnnotation``
under a name from :data:`STAGES`, so a profile captured with
``jax.profiler.start_trace`` (or xprof) shows on the host threads' lines,
next to the device's ops, which stage the host was in while the device
waited. Every name carries :data:`PREFIX`, so a trace reduction finds the
program's own spans among the Python frames and JAX's own events.

Spans are opened per socket read, per wire reply, per wire window, per
executor run, per kernel launch and per completion — never per key. Their
ids are ints (an op count, a window sequence) that link a span to the run
or window that caused it.

While no profile is being captured, :func:`stage` returns a shared no-op
context manager after one ``is_enabled`` check; nothing is recorded. The
always-on counters at the same boundaries (``WireServer.loop_busy_ns``,
``CommandExecutor.stage_ns``, ``Completer`` D2H and resolve time) are
plain integer adds kept by each layer.
"""

from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

PREFIX = "rtpu/"

#: stage -> what the span covers (the thread it runs on)
STAGES = {
    "wire.read": "parse and dispatch of one socket read (wire loop)",
    "wire.flush": "one cross-connection window into execute_many (wire loop)",
    "wire.settle": "render and pump of one completed reply (wire loop)",
    "executor.dispatch": "one run: deadline filter, journal, stage (dispatcher)",
    "backend.run": "backend.run of one run: pad, H2D, enqueue (dispatcher)",
    "backend.launch": "one engine program call of the served kinds (dispatcher)",
    "completer.d2h": "blocking materialization of one completion (completer)",
    "completer.resolve": "set_result fan-out of one completion (completer)",
}

_NAMES = {stage: PREFIX + stage for stage in STAGES}
_enabled = TraceAnnotation.is_enabled
_OFF = contextlib.nullcontext()


def stage(name: str, **ids: int):
    """Context manager: a span named ``PREFIX + name`` with integer `ids`
    while a profile is being captured, else a shared no-op."""
    if not _enabled():
        return _OFF
    return TraceAnnotation(_NAMES[name], **ids)
