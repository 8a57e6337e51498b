"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle.

Every kernel runs in interpret mode (CPU executes the kernel body) and is
asserted exactly equal (integer domain) to ref.py and the numpy oracle.
The (k, m) matrix covers the paper's schemes — RS(3,2) and RS(6,3) — plus
the minimal RS(2,1); jit caching is maximized by reusing static configs.
"""

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # fall back to the deterministic local shim
    from _hypothesis_shim import given, settings
    from _hypothesis_shim import strategies as st

from repro.core.erasure import RSCode
from repro.kernels import ops, ref
from repro.kernels.gf256_encode import (
    gf_matmul_bitsliced_batched,
    gf_scale_bitsliced,
)
from repro.kernels.xor_reduce import xor_reduce_batched


@pytest.mark.parametrize("k,m", [(2, 1), (3, 2), (6, 3)])
@pytest.mark.parametrize("length", [100, 1024])
def test_rs_encode_pallas_matches_numpy(k, m, length):
    rng = np.random.default_rng(k * 100 + m)
    data = rng.integers(0, 256, (k, length), dtype=np.uint8)
    want = RSCode(k, m).encode(data)
    got = np.asarray(ops.rs_encode(jnp.asarray(data), k, m, block_w=8))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("block_w", [8, 32])
def test_rs_encode_block_shape_sweep(block_w):
    k, m = 3, 2
    rng = np.random.default_rng(block_w)
    data = rng.integers(0, 256, (k, 32 * block_w * 2), dtype=np.uint8)
    want = RSCode(k, m).encode(data)
    got = np.asarray(ops.rs_encode(jnp.asarray(data), k, m, block_w=block_w))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,m", [(3, 2), (6, 3)])
def test_rs_encode_mxu_variant(k, m):
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (k, 1000), dtype=np.uint8)
    want = RSCode(k, m).encode(data)
    got = np.asarray(ops.rs_encode_mxu(jnp.asarray(data), k, m, block_n=128))
    assert np.array_equal(got, want)


def test_bitsliced_kernel_matches_bitsliced_ref():
    """The stream-scaling kernel keeps every (parity, chunk) product apart:
    out[:, j] is the bit-sliced oracle applied to chunk j alone."""
    from repro.core import gf256

    k, m, w = 3, 2, 32
    rng = np.random.default_rng(0)
    parity = gf256.cauchy_parity_matrix(k, m)
    bitmat = jnp.asarray(gf256.parity_bitmatrix(parity), jnp.uint32)
    planes = jnp.asarray(rng.integers(0, 2**32, (k, 8, w), dtype=np.uint32))
    got = np.asarray(gf_scale_bitsliced(bitmat, planes, m=m, k=k, block_w=8,
                                        interpret=ops._interpret()))
    for j in range(k):
        want = ref.gf_matmul_bitsliced_ref(bitmat[:, j:j + 1], planes[j:j + 1])
        assert np.array_equal(got[:, j], np.asarray(want))


@pytest.mark.parametrize("s", [1, 3])
def test_bitsliced_batched_kernel_matches_ref(s):
    """The 2D (stripe, word-block) grid equals the unbatched oracle
    applied per stripe — at the raw bit-plane level."""
    from repro.core import gf256

    k, m, w = 3, 2, 32
    rng = np.random.default_rng(s)
    parity = gf256.cauchy_parity_matrix(k, m)
    bitmat = jnp.asarray(gf256.parity_bitmatrix(parity), jnp.uint32)
    planes = jnp.asarray(rng.integers(0, 2**32, (s, k, 8, w), dtype=np.uint32))
    got = gf_matmul_bitsliced_batched(bitmat, planes, m=m, k=k, block_w=8,
                                      interpret=ops._interpret())
    want = np.stack([
        np.asarray(ref.gf_matmul_bitsliced_ref(bitmat, planes[i]))
        for i in range(s)
    ])
    assert np.array_equal(np.asarray(got), want)


def test_xor_reduce_batched_kernel():
    rng = np.random.default_rng(2)
    x = rng.integers(0, 2**32, (3, 5, 16), dtype=np.uint32)
    got = np.asarray(xor_reduce_batched(jnp.asarray(x), block_w=8,
                                        interpret=ops._interpret()))
    assert np.array_equal(got, np.bitwise_xor.reduce(x, axis=1))


def test_decode_path_via_kernel():
    code = RSCode(3, 2)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (3, 500), dtype=np.uint8)
    parity = code.encode(data)
    shards = [None, data[1], None, parity[0], parity[1]]
    got = code.decode(shards, backend="jax")
    assert np.array_equal(got, data)


@pytest.mark.parametrize("n", [2, 5])
@pytest.mark.parametrize("length", [64, 1000])
def test_xor_reduce(n, length):
    rng = np.random.default_rng(n * length)
    x = rng.integers(0, 256, (n, length), dtype=np.uint8)
    want = x[0].copy()
    for i in range(1, n):
        want ^= x[i]
    got = np.asarray(ops.xor_reduce_bytes(jnp.asarray(x)))
    assert np.array_equal(got, want)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=1, max_value=500))
def test_rs_encode_property_lengths(length):
    """Arbitrary (unaligned) lengths agree with the oracle (RS(3,2) fixed
    so the jitted kernel is compiled once)."""
    rng = np.random.default_rng(length)
    data = rng.integers(0, 256, (3, length), dtype=np.uint8)
    want = RSCode(3, 2).encode(data)
    got = np.asarray(ops.rs_encode(jnp.asarray(data), 3, 2, block_w=8))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("hkv,causal,bq,bk", [
    (2, True, 16, 32), (4, True, 32, 32), (1, False, 32, 64),
])
def test_pallas_flash_attention_matches_reference(hkv, causal, bq, bk):
    from repro.kernels.flash_attention import flash_attention_fwd

    b, s, h, d = 2, 64, 4, 16
    rng = np.random.default_rng(hkv * bq)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=causal, bq=bq, bk=bk,
                              interpret=ops._interpret())
    # reference: the (independently validated) jnp blockwise path
    from repro.models.attention import blockwise_attention

    want = blockwise_attention(q, k, v, causal, 32, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_flash_attention_ragged_seq_padding():
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.models.attention import blockwise_attention

    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 50, 6, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 50, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 50, 2, 8)), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=True, bq=16, bk=16,
                              interpret=ops._interpret())
    want = blockwise_attention(q, k, v, True, 16, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)
