"""Pallas kernels vs their XLA/numpy references (interpret mode on CPU)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from redisson_tpu import engine
from redisson_tpu.ops import hll
from redisson_tpu.ops import pallas_kernels as pk


class TestMergeStack:
    def test_matches_xla_max(self):
        rng = np.random.default_rng(0)
        stack = rng.integers(0, 52, size=(37, hll.M), dtype=np.int32)
        got = np.asarray(pk.merge_stack(jnp.asarray(stack), block=8))
        np.testing.assert_array_equal(got, stack.max(axis=0))

    def test_single_sketch(self):
        rng = np.random.default_rng(1)
        stack = rng.integers(0, 52, size=(1, hll.M), dtype=np.int32)
        got = np.asarray(pk.merge_stack(jnp.asarray(stack), block=8))
        np.testing.assert_array_equal(got, stack[0])

    def test_exact_block_multiple(self):
        rng = np.random.default_rng(2)
        stack = rng.integers(0, 52, size=(16, hll.M), dtype=np.int32)
        got = np.asarray(pk.merge_stack(jnp.asarray(stack), block=8))
        np.testing.assert_array_equal(got, stack.max(axis=0))

    def test_empty_stack(self):
        got = np.asarray(pk.merge_stack(jnp.zeros((0, hll.M), jnp.int32)))
        np.testing.assert_array_equal(got, np.zeros(hll.M, np.int32))

    def test_count_of_merge_matches(self):
        rng = np.random.default_rng(3)
        stack = rng.integers(0, 30, size=(10, hll.M), dtype=np.int32)
        merged = pk.merge_stack(jnp.asarray(stack), block=4)
        a = float(hll.count_jit(merged))
        b = float(hll.count_jit(jnp.max(jnp.asarray(stack), axis=0)))
        assert a == pytest.approx(b, rel=1e-6)


class TestPopcountCells:
    def test_matches_numpy(self):
        rng = np.random.default_rng(4)
        cells = (rng.random(100_000) < 0.3).astype(np.uint8)
        got = int(pk.popcount_cells(jnp.asarray(cells), block=4096))
        assert got == int(cells.sum())

    def test_ragged_tail(self):
        cells = np.ones(5001, np.uint8)
        assert int(pk.popcount_cells(jnp.asarray(cells), block=4096)) == 5001

    def test_empty(self):
        assert int(pk.popcount_cells(jnp.zeros((0,), jnp.uint8))) == 0


class TestBitopCells:
    @pytest.mark.parametrize("op,fn", [
        ("and", np.bitwise_and), ("or", np.bitwise_or), ("xor", np.bitwise_xor),
    ])
    def test_matches_numpy(self, op, fn):
        rng = np.random.default_rng(5)
        stack = (rng.random((3, 7001)) < 0.5).astype(np.uint8)
        got = np.asarray(pk.bitop_cells(jnp.asarray(stack), op, block=2048))
        want = fn(fn(stack[0], stack[1]), stack[2])
        np.testing.assert_array_equal(got, want)

    def test_two_operands(self):
        rng = np.random.default_rng(6)
        stack = (rng.random((2, 512)) < 0.5).astype(np.uint8)
        got = np.asarray(pk.bitop_cells(jnp.asarray(stack), "xor", block=256))
        np.testing.assert_array_equal(got, stack[0] ^ stack[1])


class TestEngineWiring:
    """The engine routes bank ops through the kernels (XLA path off-TPU,
    pallas on TPU — semantics must agree, asserted here via the engine)."""

    def test_merge_all_stacked(self):
        rng = np.random.default_rng(7)
        arrays = [jnp.asarray(rng.integers(0, 52, hll.M, dtype=np.int32))
                  for _ in range(5)]
        got = np.asarray(engine.hll_merge_all(arrays))
        want = np.max(np.stack([np.asarray(a) for a in arrays]), axis=0)
        np.testing.assert_array_equal(got, want)

    # (op, operand widths): the first operand is BITOP's destination. No
    # width is a multiple of the kernel's block (2^18 cells).
    @pytest.mark.parametrize("pallas", [False, True], ids=["lax", "kernel"])
    @pytest.mark.parametrize("op,widths", [
        ("or", (300, 300, 300)),
        ("and", (1024, 5000, 5000, 4000)),   # a fresh destination, shorter
        ("or", (1024,) + (7001,) * 7),       # the served shape, scaled down
        ("xor", (8192, 3000, 7001)),         # a destination wider than all
        ("and", (7001,)),                    # a single source
    ])
    def test_bitset_bitop(self, monkeypatch, pallas, op, widths):
        rng = np.random.default_rng(8)
        srcs = [(rng.random(w) < 0.5).astype(np.uint8) for w in widths]
        # The kernel path, in interpret mode, under a program of its own:
        # the engine's jit cache holds the lax fold for the same shapes.
        fn = engine.bitset_bitop
        if pallas:
            monkeypatch.setattr(pk, "use_pallas", lambda: True)
            fn = jax.jit(fn.__wrapped__, static_argnames=("op",))
        got = np.asarray(fn(tuple(jnp.asarray(s) for s in srcs), op))
        width = max(widths)
        want = functools.reduce(
            {"and": np.bitwise_and, "or": np.bitwise_or,
             "xor": np.bitwise_xor}[op],
            [np.pad(s, (0, width - s.size)) for s in srcs])
        np.testing.assert_array_equal(got, want)


def test_bitop_of_seven_days_into_a_fresh_key_is_one_program(monkeypatch):
    """The served read: BITOP OR of 7 daily bitmaps into a fresh key. The
    backend hands the stored states themselves to one `bitset_bitop`
    call, so no pad or stack runs outside that program, and the result
    is stored as it comes."""
    from perfbench.ref import bits
    from redisson_tpu.client import RedissonTPU
    from redisson_tpu.config import Config
    from redisson_tpu.store import ObjectType

    calls = []
    real = engine.bitset_bitop

    def spy(sources, op):
        out = real(sources, op)
        calls.append((sources, op, out))
        return out

    monkeypatch.setattr(engine, "bitset_bitop", spy)
    rng = np.random.default_rng(9)
    c = RedissonTPU.create(Config())
    try:
        store = c._routing.sketch.store
        days, ref = [f"d{i}" for i in range(7)], []
        for i, day in enumerate(days):
            top = 1500 << (i % 3)  # bitmaps 2048, 4096 and 8192 cells wide
            idx = rng.choice(top, size=top // 10, replace=False)
            c.get_bit_set(day).set_bits(idx)
            ref.append(bits.set_bits(bits.make(int(idx.max()) + 1), idx))
        wau = c.get_bit_set("wau")
        wau.or_(*days)
        assert len(calls) == 1
        sources, op, out = calls[0]
        assert op == "or" and sources[0].shape == (1024,)
        for src, day in zip(sources[1:], days):
            assert src is store.get(day, ObjectType.BITSET).state
        assert store.get("wau", ObjectType.BITSET).state is out
        want = bits.bitop("OR", *ref)
        assert wau.cardinality() == bits.bitcount(want)
        assert wau.size() == 8 * want.size
    finally:
        c.shutdown()
