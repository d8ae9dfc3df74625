"""Wire-loop time a command spent in socket-read parse and dispatch:
delta read_busy_ns / delta commands_total of WireServer.snapshot() over
the measured window, in microseconds (in a cluster both are summed over
the servers, so this is the mean over all of them)."""


def read(ctx):
    w = ctx["delta"]["wire"]
    ns, n = w.get("read_busy_ns"), w.get("commands_total")
    return None if ns is None or not n else ns / n * 1e-3
