"""The mesh write program (mesh_bank_add.device_ms_per_launch) as a share
of v5e's roofline. Its necessary work is memory: each chip reads and
writes back its quarter of the launch's padded row block, 2 x 65,536 B
(16,384 int32 registers) a row, so a launch needs 2 x 65,536 x
(delta mesh_add_block_rows / delta mesh_add_launches) / 4 bytes of each
chip, against 819 GB/s over one chip's device time a launch. A design
that gathers every row of the block on every chip reads at most 25 %.
A program without the counters or the programs reads nothing."""

from perfbench import roofline

ROW_BYTES = 65536  # one dense HLL row: 16,384 int32 registers
CHIPS = 4          # the bank is row-sharded over the cell's four chips
PROGRAMS = ("jit_hll_bank_add_bytes_rows", "jit_hll_bank_add_u64_rows",
            "jit_hll_bank_add_packed_rows")


def read(ctx):
    b = ctx["delta"]["backend"]
    t = ctx["trace"]
    launches = b.get("mesh_add_launches")
    n = sum(t["module_launches"].get(m, 0) for m in PROGRAMS)
    if not launches or not n:
        return None
    seconds = sum(t["modules"].get(m, 0.0) for m in PROGRAMS) / n
    if seconds <= 0:
        return None
    nbytes = 2 * ROW_BYTES * b["mesh_add_block_rows"] / launches / CHIPS
    return 100.0 * roofline.bound(0, nbytes, ctx["device_kind"])[
        "memory_s"] / seconds
