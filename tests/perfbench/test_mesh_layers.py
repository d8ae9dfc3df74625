"""The mesh cell's per-layer readers on a reduced four-chip trace: the
write program's time a launch per chip and its share of the HBM roofline
from the counted block rows; each reads nothing where the program has no
mesh write launches."""

import pytest

from perfbench import spec

WRITE = "jit_hll_bank_add_bytes_rows"


def _ctx(modules, launches, backend):
    return {"trace": {"modules": modules, "module_launches": launches},
            "delta": {"backend": backend}, "device_kind": "TPU v5 lite"}


@pytest.fixture
def four_chips():
    # 10 write launches, each counted on all 4 chips
    return _ctx({WRITE: 4 * 10 * 0.2e-3}, {WRITE: 40},
                {"mesh_add_launches": 100, "mesh_add_rows": 9000,
                 "mesh_add_block_rows": 12800})


def test_write_time_is_per_chip_a_launch(four_chips):
    add = spec.load_module("layers", "mesh_bank_add.device_ms_per_launch")
    assert add.read(four_chips) == pytest.approx(0.2)


def test_write_roofline_counts_each_chips_quarter_of_the_block(four_chips):
    roof = spec.load_module("layers", "mesh_bank_add_roofline")
    # 128 padded rows a launch: 2 x 64 KiB x 128 / 4 chips in 0.2 ms
    want = 100 * (2 * 65536 * 128 / 4) / 819e9 / 0.2e-3
    assert roof.read(four_chips) == pytest.approx(want)
    assert 0 < roof.read(four_chips) < 100


@pytest.mark.parametrize("name", ["mesh_bank_add.device_ms_per_launch",
                                  "mesh_bank_add_roofline"])
def test_a_program_without_the_mesh_plane_reads_nothing(name):
    ctx = _ctx({"jit_hll_bank_add_bytes_rows": 1e-3},
               {"jit_hll_bank_add_bytes_rows": 3},
               {"bank_add_launches": 3, "bank_add_rows": 30})
    assert spec.load_module("layers", name).read(ctx) is None
