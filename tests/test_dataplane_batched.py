"""Batched RS data-plane validation: rs_encode_stripes ≡ per-stripe
rs_encode ≡ the numpy LUT oracle, decode round-trips on batched stripes,
odd-length XOR folds on the kernel path, and the vectorized stream_encode
against the per-packet reference dataflow."""

import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # fall back to the deterministic local shim
    from _hypothesis_shim import given, settings
    from _hypothesis_shim import strategies as st

from repro.core import gf256
from repro.core.erasure import (
    RSCode,
    stream_encode,
    stream_encode_packets,
)
from repro.kernels import ops


SCHEMES = [(2, 1), (3, 2), (6, 3), (10, 4)]


@settings(max_examples=10, deadline=None)
@given(
    st.sampled_from(SCHEMES),
    st.integers(min_value=1, max_value=5),     # stripes
    st.integers(min_value=1, max_value=300),   # chunk length (incl. % 32 != 0)
)
def test_rs_encode_stripes_matches_loop_and_oracle(km, s, length):
    k, m = km
    rng = np.random.default_rng(k * 1000 + s * 100 + length)
    data = rng.integers(0, 256, (s, k, length), dtype=np.uint8)
    batched = np.asarray(ops.rs_encode_stripes(data, k, m, block_w=8))
    loop = np.stack(
        [np.asarray(ops.rs_encode(data[i], k, m, block_w=8)) for i in range(s)]
    )
    oracle = np.stack([gf256.gf_matmul(RSCode(k, m).parity_matrix, data[i])
                       for i in range(s)])
    assert np.array_equal(batched, loop)
    assert np.array_equal(batched, oracle)


@pytest.mark.parametrize("k,m", [(3, 2), (6, 3)])
def test_rs_encode_stripes_ref_backend(k, m):
    rng = np.random.default_rng(k)
    data = rng.integers(0, 256, (4, k, 100), dtype=np.uint8)
    got = np.asarray(ops.rs_encode_stripes(data, k, m, backend="ref"))
    want = np.asarray(ops.rs_encode_stripes(data, k, m, block_w=8))
    assert np.array_equal(got, want)


def test_rs_encode_stripes_m_zero():
    data = np.zeros((3, 4, 64), dtype=np.uint8)
    assert ops.rs_encode_stripes(data, 4, 0).shape == (3, 0, 64)


@settings(max_examples=8, deadline=None)
@given(
    st.sampled_from([(3, 2), (6, 3), (10, 4)]),
    st.integers(min_value=1, max_value=200),
    st.randoms(use_true_random=False),
)
def test_decode_stripes_roundtrip_random_erasures(km, length, rnd):
    k, m = km
    code = RSCode(k, m)
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    s = rng.integers(1, 5)
    data = rng.integers(0, 256, (s, k, length), dtype=np.uint8)
    parity = code.encode_stripes(data, backend="jax")
    shards = [data[:, i] for i in range(k)] + [parity[:, i] for i in range(m)]
    lost = rnd.sample(range(k + m), m)
    degraded = [None if i in lost else shards[i] for i in range(k + m)]
    for backend in ("jax", "numpy"):
        got = code.decode_stripes(degraded, backend=backend)
        assert np.array_equal(got, data), (km, length, lost, backend)


def test_decode_stripes_too_many_losses():
    code = RSCode(3, 2)
    data = np.zeros((2, 3, 32), dtype=np.uint8)
    parity = code.encode_stripes(data)
    degraded = [None, None, None, parity[:, 0], parity[:, 1]]
    with pytest.raises(ValueError, match="unrecoverable"):
        code.decode_stripes(degraded)


@pytest.mark.parametrize("length", [1, 3, 63, 97, 999])
def test_xor_reduce_bytes_odd_lengths_stay_on_kernel(length):
    """L % 4 != 0 pads to word granularity instead of degrading to ref."""
    rng = np.random.default_rng(length)
    x = rng.integers(0, 256, (5, length), dtype=np.uint8)
    want = np.asarray(ops.xor_reduce_bytes(x, backend="ref"))
    got = np.asarray(ops.xor_reduce_bytes(x))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("s,n,length", [(1, 2, 64), (3, 5, 100), (4, 3, 7)])
def test_xor_reduce_bytes_batched(s, n, length):
    rng = np.random.default_rng(s * n * length)
    x = rng.integers(0, 256, (s, n, length), dtype=np.uint8)
    want = np.bitwise_xor.reduce(x, axis=1)
    assert np.array_equal(np.asarray(ops.xor_reduce_bytes_batched(x)), want)
    assert np.array_equal(
        np.asarray(ops.xor_reduce_bytes_batched(x, backend="ref")), want
    )


@settings(max_examples=10, deadline=None)
@given(
    st.sampled_from([(2, 1), (3, 2), (6, 3)]),
    st.integers(min_value=1, max_value=500),
    st.sampled_from([32, 64, 129]),
    st.booleans(),
)
def test_stream_encode_vectorized_matches_per_packet(km, length, packet,
                                                     interleaved):
    k, m = km
    code = RSCode(k, m)
    rng = np.random.default_rng(length * packet)
    data = rng.integers(0, 256, (k, length), dtype=np.uint8)
    want = stream_encode_packets(
        code, data, packet_payload=packet, interleaved=interleaved,
        pool_size=512,
    )
    got = stream_encode(
        code, data, packet_payload=packet, interleaved=interleaved,
        pool_size=512,
    )
    assert np.array_equal(got, want)
    assert np.array_equal(got, code.encode(data))


@pytest.mark.parametrize("k,m,length", [(3, 2, 100), (6, 3, 33)])
def test_gf_scale_streams_matches_lut(k, m, length):
    """The bit-sliced stream-scaling kernel (TriEC data-node stage) equals
    the broadcast LUT multiply: stream (i, j) == g[i, j] * chunk_j."""
    code = RSCode(k, m)
    rng = np.random.default_rng(k * m)
    data = rng.integers(0, 256, (k, length), dtype=np.uint8)
    got = np.asarray(ops.gf_scale_streams(code.parity_matrix, data))
    want = gf256.gf_mul_vec(code.parity_matrix[:, :, None], data[None, :, :])
    assert got.shape == (m, k, length)
    assert np.array_equal(got, want)


def test_stream_encode_jax_backend_matches():
    code = RSCode(3, 2)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (3, 200), dtype=np.uint8)
    got = stream_encode(code, data, packet_payload=64, backend="jax")
    assert np.array_equal(got, code.encode(data))


@pytest.mark.parametrize("interleaved", [True, False])
def test_stream_encode_pool_model_matches_per_packet(interleaved):
    """The analytical accumulator-pressure model reproduces the per-packet
    path exactly: same success/failure verdict, same fallback count."""
    code = RSCode(3, 2)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (3, 20 * 32), dtype=np.uint8)  # 20 sequences

    def outcome(fn):
        try:
            fn(code, data, packet_payload=32, interleaved=interleaved,
               pool_size=8)
            return "ok"
        except RuntimeError as e:
            return str(e)

    assert outcome(stream_encode) == outcome(stream_encode_packets)


def test_parity_bitmatrix_memoized():
    """Same coefficient bytes -> same cached (read-only) tensor object."""
    p = gf256.cauchy_parity_matrix(3, 2)
    a = gf256.parity_bitmatrix(p)
    b = gf256.parity_bitmatrix(p.copy())
    assert a is b
    assert not a.flags.writeable
    code = RSCode(3, 2)
    assert code.parity_bitmatrix is a


@pytest.mark.parametrize("s,per_stripe", [(1, 9 << 20), (86, 9 << 20),
                                          (7, 12 << 20), (1000, 36 << 10)])
def test_dispatch_sizes_power_of_two_within_budget(s, per_stripe):
    sizes = ops._dispatch_sizes(s, per_stripe)
    assert sum(sizes) == s and sizes == sorted(sizes, reverse=True)
    assert all(n & (n - 1) == 0 for n in sizes)
    assert all(n == 1 or n * per_stripe <= ops._DISPATCH_BYTES for n in sizes)


def test_split_batch_matches_oracle(monkeypatch):
    """A batch split over several dispatches (two stripes each here)
    equals the LUT oracle, encode and decode."""
    k, m, length = 6, 3, 64
    monkeypatch.setattr(ops, "_DISPATCH_BYTES", 2 * (k + k) * length)
    code = RSCode(k, m)
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (7, k, length), dtype=np.uint8)
    parity = code.encode_stripes(data, backend="jax")
    assert np.array_equal(parity, code.encode_stripes(data, backend="numpy"))
    shards = [data[:, i] for i in range(k)] + [parity[:, i] for i in range(m)]
    degraded = [None if i in (0, 4, 5) else s for i, s in enumerate(shards)]
    assert np.array_equal(code.decode_stripes(degraded, backend="jax"), data)
