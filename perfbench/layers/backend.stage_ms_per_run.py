"""Host staging time of an executor run (backend.run: ingest planning,
padding, host-to-device copies and program enqueue), in ms: delta
stage_ns / delta runs_staged of pipeline_stats()."""


def read(ctx):
    e = ctx["delta"]["executor"]
    return e["stage_ns"] / e["runs_staged"] / 1e6 if e.get(
        "runs_staged") else None
