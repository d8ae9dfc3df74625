"""chip_smoke.py's phases at tiny size on the CPU (the lax path), and its
refusal to run without a TPU."""

import numpy as np
import pytest

import chip_smoke as cs


def test_numpy_reference_matches_native_fold():
    from redisson_tpu import native

    keys = cs.distinct_keys(20_000, 9)
    regs = np.zeros(cs.HLL_M, np.uint8)
    native.hll_fold_u64(keys, regs)
    assert np.array_equal(regs, cs.hll_registers(keys))


def test_distinct_keys_are_distinct_and_disjoint():
    a = cs.distinct_keys(5000, 1)
    b = cs.distinct_keys(5000, 1, offset=5000)
    assert np.unique(np.concatenate([a, b])).size == 10_000


@pytest.mark.parametrize("m,k", [(1 << 16, 7), (1 << 28, 7)])
def test_bloom_params_land_on_m_and_k(m, k):
    from redisson_tpu.ops import bloom_math

    n, p = cs.bloom_params(m, k)
    got = bloom_math.optimal_num_of_bits(n, p)
    assert got == m
    assert bloom_math.optimal_num_of_hash_functions(n, got) == k


def test_phase_hll_all_ingest_paths_agree():
    out = cs.phase_hll(4096)
    assert set(out) == set(cs.INGESTS)
    assert len({v["estimate"] for v in out.values()}) == 1


def test_phase_merge():
    assert cs.phase_merge(8, 500)["error"] < 0.02


def test_phase_bloom():
    out = cs.phase_bloom(2000, 1 << 16, 7)
    assert out["auto"]["fpr"] <= out["fpr_limit"]


def test_phase_bits():
    out = cs.phase_bits(1 << 14, 500)
    assert out["or"] >= out["xor"] >= out["and"]


def test_phase_wire():
    assert cs.phase_wire(300)["pfcount_union"] > 0


def test_phase_mesh_matches_stacks():
    import jax

    out = cs.phase_mesh(4, 64, 64)
    assert out["bank_devices"] == len(jax.devices())
    assert out["occupancy"] == 65


def test_check_raises_on_failure():
    with pytest.raises(cs.SmokeFailure, match="boom"):
        cs.check(False, "boom")


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_the_cpu(argv, capsys):
    assert cs.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line
    assert "no TPU found" in out.err


def test_run_phases_stops_at_the_first_failure():
    def bad():
        cs.check(False, "phase failed")

    ran = []
    with pytest.raises(cs.SmokeFailure):
        cs.run_phases((("bad", bad), ("never", lambda: ran.append(1))),
                      [object()])
    assert not ran

