"""Compile-only checks of the data-plane kernels for a described TPU v5e.

Nothing runs: each test compiles a program of the served path, at the
widths it dispatches, for one chip of a ``v5e:2x2`` topology that is
described, not attached, and asserts that the Pallas kernel is in it
(``tpu_custom_call``).  Interpret-mode tests cannot see what this
guards: a kernel body Mosaic refuses (a gather, a block breaking the
(8, 128) tiling rule) or a tile that does not fit VMEM.

Only one process at a time may load the TPU compiler's library, so the
topology is described in a fixture, never while a module is imported,
and every such test stays in this one file.  The fixture also steers
``repro.kernels.ops`` to its TPU branch (no interpret mode, 128-lane
tiles) and turns the persistent compile cache off for the module.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.erasure import RSCode
from repro.kernels import ops
from repro.kernels.gf256_encode import gf_matmul_bitsliced_batched

MiB = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("k,m", [(3, 2), (6, 3), (10, 4)])
def test_batched_encode_program_compiles(one_chip, k, m):
    """The whole fused pack -> kernel -> unpack encode program for a
    batch of 4 KiB chunks."""
    s = ops._dispatch_sizes(64, (k + m) * 4096)[0]
    assert _has_kernel(ops.gf_matmul_program(m, k, s, 4096, sharding=one_chip))


@pytest.mark.parametrize("length", [3904, 341_334], ids=["mdtest", "save"])
def test_off_tile_chunk_program_compiles(one_chip, length):
    """RS(6,3) encode of a chunk off the kernel tile (an mdtest-hard
    file's 3,904-byte chunk, a checkpoint leaf's 341 kB one): the pad and
    the trim compile inside the one program the dispatch runs."""
    assert length % (32 * ops._pick_block_w(length, None))
    assert _has_kernel(ops.gf_matmul_program(3, 6, 1, length,
                                             sharding=one_chip))


@pytest.mark.parametrize("n,k", [(3, 6), (6, 6)], ids=["encode", "decode"])
def test_rs63_kernel_compiles_at_1mib_cell(one_chip, n, k):
    """RS(6,3) encode and the 6x6 decode at HDFS's 1 MiB cell, with the
    tile and the dispatch size the data plane picks for it."""
    length = 1 * MiB
    bw = ops._pick_block_w(length, None)
    assert bw % 128 == 0
    s = ops._dispatch_sizes(1000, (k + n) * length)[0]
    bitmat = jax.ShapeDtypeStruct((n, k, 8, 8), jnp.uint32, sharding=one_chip)
    planes = jax.ShapeDtypeStruct((s, k, 8, length // 32), jnp.uint32,
                                  sharding=one_chip)
    kernel = jax.jit(lambda b, p: gf_matmul_bitsliced_batched(
        b, p, m=n, k=k, block_w=bw, interpret=ops._interpret()))
    assert _has_kernel(kernel.lower(bitmat, planes))


def test_gf_scale_program_compiles(one_chip):
    code = RSCode(6, 3)
    length = 4096
    bw = ops._pick_block_w(length, None)
    bitmat = jax.ShapeDtypeStruct((3, 6, 8, 8), jnp.uint32, sharding=one_chip)
    data = jax.ShapeDtypeStruct((code.k, length), jnp.uint8, sharding=one_chip)
    assert _has_kernel(
        ops._scale_planes.lower(bitmat, data, bw, ops._interpret()))


def test_xor_reduce_batched_program_compiles(one_chip):
    x = jax.ShapeDtypeStruct((3, 6, 4096 + 3), jnp.uint8, sharding=one_chip)
    assert _has_kernel(jax.jit(ops.xor_reduce_bytes_batched).lower(x))
