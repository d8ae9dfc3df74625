"""Device time of one launch of the mesh data plane's HLL write program,
per chip, in ms, from the trace: the multi-target insert
(engine.hll_bank_add_bytes_rows for the wire's byte keys, and its u64
and packed twins) with a mesh retires a window's PFADDs into the bank
row-sharded over the chips. The trace counts a launch once on every
chip, so device seconds over launches is one chip's time. It reads only
where the program counts mesh write launches (mesh_add_launches): a
program without the mesh plane reads nothing."""

PROGRAMS = ("jit_hll_bank_add_bytes_rows", "jit_hll_bank_add_u64_rows",
            "jit_hll_bank_add_packed_rows")


def read(ctx):
    t = ctx["trace"]
    n = sum(t["module_launches"].get(m, 0) for m in PROGRAMS)
    if not n or not ctx["delta"]["backend"].get("mesh_add_launches"):
        return None
    return 1e3 * sum(t["modules"].get(m, 0.0) for m in PROGRAMS) / n
