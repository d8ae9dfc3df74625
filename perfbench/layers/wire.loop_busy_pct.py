"""Share of the window the wire loop thread spent in its own stages
(socket-read parse and dispatch, window flush, reply settle): delta
loop_busy_ns of WireServer.snapshot() over the measured window."""


def read(ctx):
    ns = ctx["delta"]["wire"].get("loop_busy_ns")
    return None if ns is None else 100.0 * ns / (ctx["seconds"] * 1e9)
