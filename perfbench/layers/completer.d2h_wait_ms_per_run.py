"""Time the completer thread blocks materializing one completion's
device results on the host, in ms: delta d2h_wait_ns / delta
runs_materialized of the backend's counters."""


def read(ctx):
    b = ctx["delta"]["backend"]
    return b["d2h_wait_ns"] / b["runs_materialized"] / 1e6 if b.get(
        "runs_materialized") else None
