"""Ahead-of-time compiles of every Pallas kernel the chip path reaches, for
a described TPU v5e, at the widths the served path uses.

Nothing runs on a chip here: each test lowers and compiles one kernel for
a `v5e:2x2` topology that is described, not attached, and checks that
the compiled program holds the Mosaic kernel (`tpu_custom_call`) — what
the chip's compiler refuses, these tests refuse. Results are checked by
the interpret-mode tests (test_pallas, test_ingest, test_tape) and on the
chip by chip_smoke.py.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


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


@pytest.mark.parametrize("t,l", [(64, 16384), (2, 1024)])
def test_delta_merge(one_chip, t, l):
    from redisson_tpu.ops import pallas_kernels as pk

    _compile(one_chip, lambda a, b: pk.delta_merge(a, b, interpret=False),
             ((t, l), jnp.uint8), ((t, l), jnp.uint8))


def test_popcount_partials(one_chip):
    from redisson_tpu.ops import pallas_kernels as pk

    _compile(one_chip, lambda c: pk.popcount_partials(c, interpret=False),
             ((1 << 28,), jnp.uint8))


@pytest.mark.parametrize("t,l", [(64, 16384), (4, 1024)])
def test_window_merge_pallas(one_chip, t, l):
    from redisson_tpu.ops import window_kernel as wk

    _compile(one_chip,
             lambda o, w, tab: wk.window_merge_pallas(o, w, tab,
                                                      interpret=False),
             ((t, l), jnp.uint8), ((t, l), jnp.uint8),
             ((t, wk.TABLE_COLS), jnp.int32))


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
