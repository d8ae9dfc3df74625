"""Ahead-of-time compiles of every Pallas kernel the chip path reaches, for
a described TPU v5e, at the widths the served path uses; and of the mesh
data plane's bank programs on a described four-chip v5e at the served
bank size (262,144 rows, 4 GiB a chip), whose per-chip memory and
collectives the compiler reports.

Nothing runs on a chip here: each test lowers and compiles one kernel for
a `v5e:2x2` topology that is described, not attached, and checks that
the compiled program holds the Mosaic kernel (`tpu_custom_call`) — what
the chip's compiler refuses, these tests refuse. Results are checked by
the interpret-mode tests (test_pallas, test_ingest, test_delta_window)
and on the chip by chip_smoke.py.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

GIB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """The cluster mesh data plane's four-chip slot mesh."""
    import numpy as np
    from jax.sharding import Mesh

    from redisson_tpu.parallel.mesh import SLOT_AXIS

    return Mesh(np.array(topo.devices), (SLOT_AXIS,))


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_merge_stack(one_chip):
    from redisson_tpu.ops import pallas_kernels as pk

    _compile(one_chip, lambda s: pk.merge_stack(s, interpret=False),
             ((1024, 16384), jnp.int32))


def test_bitop_cells(one_chip):
    from redisson_tpu.ops import pallas_kernels as pk

    _compile(one_chip, lambda s: pk.bitop_cells(s, "xor", interpret=False),
             ((4, 1 << 26), jnp.uint8))


def test_bitset_bitop_at_served_shape(one_chip, monkeypatch):
    """BITOP OR of 7 daily 2^27-cell bitmaps into a fresh 1,024-cell key,
    as one program: the select that builds the [8, 2^27] stack writes
    each operand once, with no padded [1, 2^27] relayout of its own."""
    from redisson_tpu import engine
    from redisson_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    n = 1 << 27
    srcs = tuple(jax.ShapeDtypeStruct((w,), jnp.uint8, sharding=one_chip)
                 for w in (1024,) + (n,) * 7)
    fn = jax.jit(engine.bitset_bitop.__wrapped__, static_argnames=("op",))
    compiled = fn.lower(srcs, "or").compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the 1 GiB [8, 2^27] stack and little else
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.25 * GIB
    assert f"[1,{n}]" not in text and f"[1,1,{n}]" not in text


@pytest.mark.parametrize("t,l", [(64, 16384), (2, 1024)])
def test_delta_merge(one_chip, t, l):
    from redisson_tpu.ops import pallas_kernels as pk

    _compile(one_chip, lambda a, b: pk.delta_merge(a, b, interpret=False),
             ((t, l), jnp.uint8), ((t, l), jnp.uint8))


def test_popcount_partials(one_chip):
    from redisson_tpu.ops import pallas_kernels as pk

    _compile(one_chip, lambda c: pk.popcount_partials(c, interpret=False),
             ((1 << 28,), jnp.uint8))


# The segmented kernels compile without their XLA sort (a 2^21-key sort
# alone takes ~15 s to compile): segment_fold is the Pallas launch.
@pytest.mark.parametrize("n", [1 << 16, 1 << 21])
def test_hll_insert_segmented(one_chip, n):
    from redisson_tpu.ingest import kernels as ik

    tiles = 16384 // 1024
    _compile(one_chip,
             lambda r, c, s: ik.segment_fold(r, c, s, shift=6, tile=1024,
                                             interpret=False),
             ((16384,), jnp.int32), ((n + 1024,), jnp.int32),
             ((tiles + 1,), jnp.int32))


def test_bits_insert_segmented(one_chip):
    from redisson_tpu.ingest import kernels as ik

    tile = 1 << 12  # 2^28 cells: the tile grows until the spans fit SMEM
    _compile(one_chip,
             lambda r, c, s: ik.segment_fold(r, c, s, shift=0, tile=tile,
                                             interpret=False),
             ((1 << 28,), jnp.uint8), (((1 << 20) + 1024,), jnp.int32),
             (((1 << 28) // tile + 1,), jnp.int32))


# The mesh data plane's bank programs at the served size: a 262,144-row
# bank, 4 GiB a chip. No chip ever holds more than its quarter of the
# bank plus one doubling, and no program gathers the bank.

BANK_ROWS = 262144


def _mesh_compile(mesh4, fn, *shapes, **static):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from redisson_tpu.parallel.mesh import SLOT_AXIS

    shards = {"bank": NamedSharding(mesh4, P(SLOT_AXIS, None)),
              "rep": NamedSharding(mesh4, P())}
    args = [jax.ShapeDtypeStruct(s, d, sharding=shards[k])
            for s, d, k in shapes]
    compiled = fn.lower(*args, **static).compile()
    text = compiled.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    return compiled.memory_analysis(), text


def test_mesh_bank_add_at_served_size(mesh4):
    from redisson_tpu import engine

    n, r = 1024, 256
    mem, text = _mesh_compile(
        mesh4, engine.hll_bank_add_bytes_rows,
        ((BANK_ROWS, 16384), jnp.int32, "bank"), ((n, 8), jnp.uint8, "rep"),
        ((n,), jnp.int32, "rep"), ((n,), jnp.int32, "rep"),
        ((r,), jnp.int32, "rep"), ((n,), jnp.bool_, "rep"), mesh=mesh4)
    # the chip's quarter of the bank is updated in place
    assert mem.alias_size_in_bytes == 4 * GIB
    assert mem.temp_size_in_bytes < 64 << 20
    assert "all-reduce" in text  # the one pmax of the changed flags


def test_mesh_bank_grow_to_served_size(mesh4):
    from redisson_tpu import engine

    mem, text = _mesh_compile(
        mesh4, engine.hll_bank_grow_mesh,
        ((BANK_ROWS // 2, 16384), jnp.int32, "bank"), new_cap=BANK_ROWS,
        mesh=mesh4)
    assert mem.argument_size_in_bytes == 2 * GIB
    assert mem.output_size_in_bytes == 4 * GIB
    assert mem.temp_size_in_bytes < 128 << 20  # one 64 MiB piece in flight
    assert "collective-permute" in text


def test_mesh_union_count_at_served_size(mesh4):
    from redisson_tpu import engine

    count = engine._mesh_collectives(mesh4)["count"]
    mem, text = _mesh_compile(
        mesh4, count, ((BANK_ROWS, 16384), jnp.int32, "bank"),
        ((8,), jnp.int32, "rep"))
    assert mem.temp_size_in_bytes < 16 << 20
    assert "all-reduce" in text
