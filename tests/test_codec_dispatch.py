"""One device program per codec dispatch: ``gf_matmul_bytes_batched``
hands the unpadded (S, k, L) piece to ``_encode_planes_batched``, which
pads L to the kernel tile and trims its (S, n, L) result itself, so no
eager pad or slice runs beside it."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf256
from repro.core.erasure import RSCode
from repro.kernels import ops

PARITY = RSCode(6, 3).parity_matrix


def _tile(length: int) -> int:
    return 32 * ops._pick_block_w(length)


def _check(data: np.ndarray, coeffs: np.ndarray = PARITY) -> None:
    out = ops.to_host(ops.gf_matmul_bytes_batched(coeffs, data))
    s, _, length = data.shape
    assert out.shape == (s, coeffs.shape[0], length)
    want = np.stack([gf256.gf_matmul(coeffs, stripe) for stripe in data])
    assert np.array_equal(out, want)


@pytest.mark.parametrize("length", [1, 31, 3904, 4096, 43691, 65537])
def test_byte_exact_against_the_lut_at_any_chunk_length(length):
    data = np.random.default_rng(length).integers(
        0, 256, (1, 6, length), dtype=np.uint8)
    _check(data)


@pytest.mark.parametrize("s,length", [(3, 31), (5, 3904), (7, 4096)])
def test_byte_exact_when_the_batch_splits_into_pieces(s, length):
    assert len(ops._dispatch_sizes(s, 9 * length)) > 1
    data = np.random.default_rng(s * length).integers(
        0, 256, (s, 6, length), dtype=np.uint8)
    _check(data)


def test_byte_exact_when_the_dispatch_cap_splits_the_batch(monkeypatch):
    """A cap of one stripe a piece: each piece is its own program."""
    length = 3904
    monkeypatch.setattr(ops, "_DISPATCH_BYTES", 9 * length)
    assert ops._dispatch_sizes(3, 9 * length) == [1, 1, 1]
    data = np.random.default_rng(7).integers(
        0, 256, (3, 6, length), dtype=np.uint8)
    _check(data)


def test_decode_matrix_trims_to_the_chunk_length():
    inv = gf256.gf_mat_inv(RSCode(6, 3).generator[[1, 2, 3, 4, 5, 6]])
    data = np.random.default_rng(3).integers(0, 256, (2, 6, 43691),
                                             dtype=np.uint8)
    _check(data, inv)


@pytest.mark.parametrize("length", [31, 3904])
def test_an_unaligned_dispatch_is_one_program(monkeypatch, length):
    """After a warm-up, the dispatch makes no eager ``jnp.pad``, the
    program gets the unpadded piece, and its output is the result as it
    is, not a slice of it."""
    assert length % _tile(length)
    data = np.random.default_rng(length).integers(
        0, 256, (1, 6, length), dtype=np.uint8)
    ops.to_host(ops.gf_matmul_bytes_batched(PARITY, data))   # warm-up

    def no_pad(*args, **kwargs):
        raise AssertionError("eager jnp.pad outside the codec program")

    monkeypatch.setattr(jnp, "pad", no_pad)
    program = ops._encode_planes_batched
    calls = []

    def spy(bitmat, piece, block_w, interpret):
        out = program(bitmat, piece, block_w, interpret)
        calls.append((piece.shape, out))
        return out

    monkeypatch.setattr(ops, "_encode_planes_batched", spy)
    got = ops.gf_matmul_bytes_batched(PARITY, data)
    assert [shape for shape, _ in calls] == [(1, 6, length)]
    assert got is calls[0][1]
    assert got.shape == (1, 3, length)


@pytest.mark.parametrize("s,length,padded", [
    (1, 3904, 1), (3, 31, 2), (1, 4096, 0), (3, 256, 0)])
def test_padded_counts_each_piece_the_program_pads(s, length, padded):
    assert (length % _tile(length) != 0) == (padded > 0)
    data = np.zeros((s, 6, length), np.uint8)
    before = dict(ops.CODEC_COUNTS)
    ops.to_host(ops.gf_matmul_bytes_batched(PARITY, data))
    pieces = len(ops._dispatch_sizes(s, 9 * length))
    assert ops.CODEC_COUNTS["dispatches"] == before["dispatches"] + pieces
    assert ops.CODEC_COUNTS["padded"] == before["padded"] + padded
