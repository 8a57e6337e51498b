"""RS(k, m) coding invariants: MDS recovery, streaming == whole-stripe."""

import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # fall back to the deterministic local shim
    from _hypothesis_shim import given, settings
    from _hypothesis_shim import strategies as st

from repro.core.erasure import (
    AccumulatorPool,
    RSCode,
    join_stripe,
    split_stripe,
    stream_encode,
)


@settings(max_examples=30, deadline=None)
@given(
    st.integers(min_value=1, max_value=8),      # k
    st.integers(min_value=0, max_value=4),      # m
    st.integers(min_value=1, max_value=400),    # payload length
    st.randoms(use_true_random=False),
)
def test_any_m_losses_recover(k, m, length, rnd):
    code = RSCode(k, m)
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    data = rng.integers(0, 256, (k, length), dtype=np.uint8)
    parity = code.encode(data)
    shards = list(data) + list(parity)
    lost = rnd.sample(range(k + m), m)
    degraded = [None if i in lost else shards[i] for i in range(k + m)]
    assert np.array_equal(code.decode(degraded), data)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_one_stripe_views_equal_the_stripes_path(backend):
    """``encode`` / ``decode`` are the S = 1 views of ``encode_stripes`` /
    ``decode_stripes``, on the host LUT path and on the kernels."""
    code = RSCode(6, 3)
    data = np.random.default_rng(6).integers(0, 256, (6, 3901), np.uint8)
    parity = code.encode_stripes(data[None], backend=backend)[0]
    assert np.array_equal(code.encode(data, backend=backend), parity)
    shards = [None, *data[1:5], None, *parity]
    want = code.decode_stripes(
        [None if s is None else s[None] for s in shards], backend=backend)[0]
    assert np.array_equal(want, data)
    assert np.array_equal(code.decode(shards, backend=backend), want)


def test_more_than_m_losses_fail():
    code = RSCode(4, 2)
    data = np.arange(4 * 64, dtype=np.uint8).reshape(4, 64)
    parity = code.encode(data)
    shards = [None, None, None, data[3], parity[0], parity[1]]
    with pytest.raises(ValueError, match="unrecoverable"):
        code.decode(shards)


@settings(max_examples=15, deadline=None)
@given(
    st.sampled_from([(2, 1), (3, 2), (6, 3)]),
    st.integers(min_value=1, max_value=600),
    st.sampled_from([32, 64, 129]),
    st.booleans(),
)
def test_stream_encode_matches_batch(km, length, packet, interleaved):
    k, m = km
    code = RSCode(k, m)
    rng = np.random.default_rng(length * packet)
    data = rng.integers(0, 256, (k, length), dtype=np.uint8)
    got = stream_encode(
        code, data, packet_payload=packet, interleaved=interleaved,
        pool_size=512,
    )
    assert np.array_equal(got, code.encode(data))


def test_reconstruct_single_shard():
    code = RSCode(5, 3)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (5, 96), dtype=np.uint8)
    parity = code.encode(data)
    shards = list(data) + list(parity)
    for idx in range(8):
        degraded = [s if i != idx else None for i, s in enumerate(shards)]
        rebuilt = code.reconstruct_shard(degraded, idx)
        assert np.array_equal(rebuilt, shards[idx]), idx


@given(st.binary(min_size=0, max_size=2000), st.integers(min_value=1, max_value=7))
@settings(max_examples=30, deadline=None)
def test_split_join_roundtrip(blob, k):
    chunks = split_stripe(blob, k)
    assert chunks.shape[0] == k and chunks.shape[1] % 32 == 0
    assert join_stripe(chunks, len(blob)) == blob


# -- batched decode: the m-erasure boundary (degraded reads / repair) --------


def _stripe_shards(code, data):
    """(S, k, L) data -> the k+m per-slot (S, L) shard batches."""
    parity = code.encode_stripes(data, backend="numpy")
    return ([data[:, i, :] for i in range(code.k)]
            + [parity[:, i, :] for i in range(code.m)])


@settings(max_examples=15, deadline=None)
@given(
    st.sampled_from([(3, 2), (6, 3), (10, 4)]),
    st.integers(min_value=1, max_value=4),       # erasure count (capped to m)
    st.sampled_from([1, 33, 97, 255, 501]),      # odd chunk sizes
    st.integers(min_value=1, max_value=3),       # stripes per batch
    st.randoms(use_true_random=False),
)
def test_decode_stripes_roundtrip_any_le_m_erasures(km, r, length, s, rnd):
    """encode -> drop any <= m shards -> decode_stripes recovers bit-exact
    (the degraded-read invariant, batched across same-pattern stripes)."""
    k, m = km
    r = min(r, m)
    code = RSCode(k, m)
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    data = rng.integers(0, 256, (s, k, length), dtype=np.uint8)
    shards = _stripe_shards(code, data)
    lost = rnd.sample(range(k + m), r)
    degraded = [None if i in lost else shards[i] for i in range(k + m)]
    got = code.decode_stripes(degraded, backend="numpy")
    assert np.array_equal(got, data), (km, r, length, s, lost)


@settings(max_examples=9, deadline=None)
@given(
    st.sampled_from([(3, 2), (6, 3), (10, 4)]),
    st.sampled_from([31, 65, 127]),
    st.randoms(use_true_random=False),
)
def test_decode_stripes_m_erasure_boundary(km, length, rnd):
    """Exactly m erasures (the MDS boundary) recover; m+1 must raise."""
    k, m = km
    code = RSCode(k, m)
    rng = np.random.default_rng(length * k)
    data = rng.integers(0, 256, (2, k, length), dtype=np.uint8)
    shards = _stripe_shards(code, data)
    at_boundary = rnd.sample(range(k + m), m)
    degraded = [None if i in at_boundary else shards[i]
                for i in range(k + m)]
    assert np.array_equal(code.decode_stripes(degraded, backend="numpy"),
                          data)
    beyond = rnd.sample(range(k + m), m + 1)
    too_degraded = [None if i in beyond else shards[i]
                    for i in range(k + m)]
    with pytest.raises(ValueError, match="unrecoverable"):
        code.decode_stripes(too_degraded, backend="numpy")


def test_decode_stripes_jax_backend_matches_numpy():
    """The fused-kernel decode path is bit-identical to the host LUT."""
    code = RSCode(3, 2)
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, (4, 3, 129), dtype=np.uint8)
    shards = _stripe_shards(code, data)
    degraded = [None, shards[1], None, shards[3], shards[4]]
    want = code.decode_stripes(degraded, backend="numpy")
    got = code.decode_stripes(degraded, backend="jax")
    assert np.array_equal(got, want)
    assert np.array_equal(got, data)


@settings(max_examples=10, deadline=None)
@given(
    st.sampled_from([(3, 2), (6, 3), (10, 4)]),
    st.sampled_from([1, 33, 97, 255]),           # odd payloads stay on-kernel
    st.randoms(use_true_random=False),
)
def test_xor_reduce_bytes_aggregates_parity_reconstruction(km, length, rnd):
    """The parity-node XOR aggregation (kernel XOR fold over the k
    scaled intermediate-parity streams) equals reconstructing that parity
    shard from the surviving k — the streaming dataflow and the decode
    solver agree at the erasure boundary, for odd chunk sizes."""
    from repro.core import gf256
    from repro.kernels import ops

    k, m = km
    code = RSCode(k, m)
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    data = rng.integers(0, 256, (k, length), dtype=np.uint8)
    i = rnd.randrange(m)
    inter = gf256.gf_mul_vec(data, code.parity_matrix[i][:, None])  # (k, L)
    agg = np.asarray(ops.xor_reduce_bytes_batched(inter[None]))[0]
    shards = list(data) + list(code.encode(data))
    shards[k + i] = None
    assert np.array_equal(agg, code.reconstruct_shard(shards, k + i))


def test_accumulator_pool_exhaustion_and_reuse():
    pool = AccumulatorPool(2, payload_size=16)
    a = pool.allocate()
    b = pool.allocate()
    assert pool.allocate() is None          # exhausted -> CPU fallback path
    pool.xor_into(a, np.full(16, 0xAA, np.uint8))
    pool.xor_into(a, np.full(16, 0x0F, np.uint8))
    out = pool.release(a)
    assert (out == (0xAA ^ 0x0F)).all()
    c = pool.allocate()                     # freed slot is reusable and zeroed
    assert c is not None
    assert (pool.release(c) == 0).all()
    assert pool.high_watermark == 2
