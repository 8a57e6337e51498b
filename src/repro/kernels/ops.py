"""The one dispatcher from the coding layer to the Pallas kernels.

:func:`gf_matmul_bytes_batched` is the data plane's only way into the
bit-sliced GF(2^8) matmul: ``RSCode`` calls it for every encode and
decode.  It owns padding, bit-plane packing, the dispatch split and the
tile (:func:`_pick_block_w`), and counts its transfers in
``CODEC_COUNTS``; :func:`to_host` brings a result back.  On a TPU the
kernels compile natively; everywhere else they run in interpret mode
(the same kernel body, Python-executed), so all of it is testable on a
CPU.  :func:`dataplane_backend` is the one place that chooses between
the kernels and the host LUT path: the kernels on a TPU, numpy elsewhere.

:func:`gf_scale_streams` and :func:`xor_reduce_bytes_batched` are the
paper's streaming aggregation (``erasure.stream_encode``), the data-node
scaling and the parity-node XOR fold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gf256
from repro.kernels import ref
from repro.kernels.gf256_encode import (
    gf_matmul_bitsliced_batched,
    gf_scale_bitsliced,
)
from repro.kernels.xor_reduce import xor_reduce_batched as _xor_reduce_batched
from repro.trace import wall


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


#: The codec's transfers and dispatches since the process started, always
#: on: read them as differences between two snapshots.  ``dispatches`` /
#: ``stripes`` count :func:`gf_matmul_bytes_batched`'s fused dispatches
#: and the stripes they carried, ``padded`` the dispatches whose chunk
#: length the program padded to its tile, ``h2d_bytes`` the host stripe
#: bytes it moved to the device, ``d2h_bytes`` what :func:`to_host`
#: brought back.
CODEC_COUNTS = dict.fromkeys(("dispatches", "stripes", "padded",
                              "h2d_bytes", "d2h_bytes"), 0)


def to_host(x: jax.Array) -> np.ndarray:
    """The codec's result as a host array: waits for the device and
    copies, counted in ``CODEC_COUNTS["d2h_bytes"]``."""
    with wall.span("codec.d2h", "coding"):
        out = np.asarray(x)
    CODEC_COUNTS["d2h_bytes"] += out.nbytes
    return out


def dataplane_backend(backend: str | None = None) -> str:
    """The RS data plane's backend: ``backend`` when the caller names one,
    else the Pallas kernels (``"jax"``) on a TPU and the numpy LUT path
    (``"numpy"``) on any other platform, where the kernels would only be
    interpreted."""
    if backend is not None:
        return backend
    return "jax" if _on_tpu() else "numpy"


# ---------------------------------------------------------------------------
# RS encode / GF matmul on byte streams.
# ---------------------------------------------------------------------------


def _pad_to(x: jax.Array, mult: int, axis: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad), size


@functools.lru_cache(maxsize=256)
def _bitmat_device(coeff_bytes: bytes, n: int, k: int) -> jax.Array:
    """Device-resident (n, k, 8, 8) coefficient bit-matrix tensor.

    Memoized by coefficient bytes on top of the host-side
    ``gf256.parity_bitmatrix`` cache, so steady-state encode/decode calls
    skip both the nested-loop numpy build and the host->device upload.
    """
    coeffs = np.frombuffer(coeff_bytes, dtype=np.uint8).reshape(n, k)
    return jnp.asarray(gf256.parity_bitmatrix(coeffs), dtype=jnp.uint32)


def _clamp_block_w(words: int) -> int:
    """Adaptive words-per-VMEM-tile: the smallest covering multiple of the
    tile granule, capped at 2048 — small payloads stop padding out to a
    full-size tile, large ones amortize per-grid-step overhead across
    wider lanes.  The granule is 128 when compiling for a real TPU (the
    lane-dimension requirement Mosaic enforces) and 8 in interpret mode
    (keeps CPU-validation shapes small)."""
    granule = 128 if _on_tpu() else 8
    return max(granule, min(2048, -(-words // granule) * granule))


def _pick_block_w(length: int) -> int:
    """Tile for a ``length``-byte chunk (32 bytes/packed word)."""
    return _clamp_block_w(-(-length // 32))


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def _encode_planes_batched(bitmat, data_bytes, block_w, interpret):
    """Fused pipeline under one jit: pad the chunk length L to the kernel
    tile -> bit-plane pack -> single batched Pallas dispatch over the
    (stripe, word-block) grid -> unpack -> trim back to L, so a dispatch
    is one device program whatever L is.  The pad and pack ops carry the
    ``rs_pack`` scope in their op names, the unpack and trim ``rs_unpack``,
    the kernel is ``rs_gf_matmul``."""
    length = data_bytes.shape[2]
    with jax.named_scope("rs_pack"):
        data_bytes, _ = _pad_to(data_bytes, 32 * block_w, axis=2)
        planes = ref.pack_bitplanes(data_bytes)      # (S, k, 8, w)
    m, k = bitmat.shape[0], bitmat.shape[1]
    out_planes = gf_matmul_bitsliced_batched(
        bitmat, planes, m=m, k=k, block_w=block_w, interpret=interpret
    )
    with jax.named_scope("rs_unpack"):
        return ref.unpack_bitplanes(out_planes)[:, :, :length]  # (S, m, L)


#: Payload bytes, (k + n) * L per stripe, that one fused dispatch may
#: carry.  XLA materialises the bit-plane pack and unpack as uint32
#: temporaries in HBM, 66-96x the dispatch's output bytes in the programs
#: compiled for a v5e: one dispatch of a 6x6 decode over 64 stripes of
#: 1 MiB would need 24.75 GiB, more than the chip's 16 GB.
_DISPATCH_BYTES = 64 << 20


def _dispatch_sizes(s: int, per_stripe: int) -> list[int]:
    """Split an S-stripe batch into power-of-two dispatches, largest
    first, each of at most ``_DISPATCH_BYTES`` (and at least one stripe):
    whatever S callers send, a (geometry, chunk length) compiles at most
    log2(cap) + 1 programs."""
    cap = 1 << (max(1, _DISPATCH_BYTES // max(per_stripe, 1)).bit_length() - 1)
    sizes = []
    while s > 0:
        size = min(cap, 1 << (s.bit_length() - 1))
        sizes.append(size)
        s -= size
    return sizes


def gf_matmul_bytes_batched(
    coeffs: np.ndarray | jax.Array,
    data: np.ndarray | jax.Array,
) -> jax.Array:
    """(n, k) GF coefficient bytes x (S, k, L) stripe batch -> (S, n, L).

    Used for both encode (coeffs = parity matrix) and decode (coeffs =
    inverted generator submatrix).  Stripes share one coefficient upload
    and one fused pack/matmul/unpack dispatch per :func:`_dispatch_sizes`
    piece; host arrays move to the device one piece at a time.  The tile
    is :func:`_pick_block_w` of L.
    """
    if not isinstance(data, jax.Array):
        data = np.asarray(data, dtype=np.uint8)
    assert data.ndim == 3, data.shape
    coeffs_np = np.ascontiguousarray(coeffs, dtype=np.uint8)
    n, k = coeffs_np.shape
    s, kk, length = data.shape
    assert kk == k, (coeffs_np.shape, data.shape)
    if n == 0 or s == 0:
        return jnp.zeros((s, n, length), dtype=jnp.uint8)
    bw = _pick_block_w(length)
    padded = length % (32 * bw) != 0
    bitmat = _bitmat_device(coeffs_np.tobytes(), n, k)
    outs, lo = [], 0
    for size in _dispatch_sizes(s, (k + n) * length):
        piece = data[lo:lo + size]
        if not isinstance(piece, jax.Array):
            CODEC_COUNTS["h2d_bytes"] += piece.nbytes
        with wall.span("codec.h2d", "coding"):
            piece = jnp.asarray(piece, dtype=jnp.uint8)
        with wall.span("codec.launch", "coding"):
            outs.append(_encode_planes_batched(bitmat, piece, bw, _interpret()))
        CODEC_COUNTS["dispatches"] += 1
        CODEC_COUNTS["stripes"] += size
        CODEC_COUNTS["padded"] += padded
        lo += size
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def gf_matmul_program(n: int, k: int, s: int, length: int, sharding=None):
    """The fused pack/matmul/unpack program :func:`gf_matmul_bytes_batched`
    dispatches for an (n, k) coefficient matrix and an (s, k, L) piece,
    lowered for this platform (or for ``sharding``'s device) but not run:
    its text names ``tpu_custom_call`` when the kernel compiles natively."""
    bw = _pick_block_w(length)
    bitmat = jax.ShapeDtypeStruct((n, k, 8, 8), jnp.uint32, sharding=sharding)
    data = jax.ShapeDtypeStruct((s, k, length), jnp.uint8, sharding=sharding)
    return _encode_planes_batched.lower(bitmat, data, bw, _interpret())


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def _scale_planes(bitmat, data_bytes, block_w, interpret):
    """Fused pack -> bit-sliced stream scaling -> unpack, one jit."""
    planes = ref.pack_bitplanes(data_bytes)          # (k, 8, w)
    m, k = bitmat.shape[0], bitmat.shape[1]
    out_planes = gf_scale_bitsliced(
        bitmat, planes, m=m, k=k, block_w=block_w, interpret=interpret
    )
    return ref.unpack_bitplanes(out_planes)          # (m, k, L)


def gf_scale_streams(
    coeffs: np.ndarray | jax.Array,
    data: jax.Array,
) -> jax.Array:
    """(m, k) GF coefficients x (k, L) chunks -> (m, k, L) scaled streams.

    The data-node stage of streaming TriEC: stream (i, j) is
    g[i, j] * chunk_j, every (parity, chunk) pair in one fused dispatch —
    no folding, so the parity-node XOR aggregation stays a separate
    (batched) stage.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    coeffs_np = np.ascontiguousarray(coeffs, dtype=np.uint8)
    m, k = coeffs_np.shape
    assert data.shape[0] == k, (coeffs_np.shape, data.shape)
    if m == 0:
        return jnp.zeros((0, k, data.shape[1]), dtype=jnp.uint8)
    bw = _pick_block_w(data.shape[1])
    data_p, orig = _pad_to(data, 32 * bw, axis=1)
    bitmat = _bitmat_device(coeffs_np.tobytes(), m, k)
    out = _scale_planes(bitmat, data_p, bw, _interpret())
    return out[:, :, :orig]


# ---------------------------------------------------------------------------
# XOR aggregation.
# ---------------------------------------------------------------------------


def xor_reduce_bytes_batched(x: jax.Array) -> jax.Array:
    """Batched XOR-fold: (S, n, L) uint8 over axis 1 -> (S, L) uint8.

    The parity-node accumulator aggregation for S concurrent sequences in
    a single 2D-grid kernel dispatch (paper section VI-B3, batched).
    Odd-sized payloads are zero-padded to uint32 word granularity and
    sliced back, so every L stays on the kernel path (XOR of zero is a
    no-op).
    """
    x = jnp.asarray(x, dtype=jnp.uint8)
    assert x.ndim == 3, x.shape
    s, n, L = x.shape
    xp, _ = _pad_to(x, 4, axis=2)
    words = jax.lax.bitcast_convert_type(
        xp.reshape(s, n, -1, 4), jnp.uint32
    ).reshape(s, n, -1)
    bw = _clamp_block_w(words.shape[2])
    words_p, orig = _pad_to(words, bw, axis=2)
    out = _xor_reduce_batched(words_p, block_w=bw, interpret=_interpret())[:, :orig]
    out_bytes = jax.lax.bitcast_convert_type(out[..., None], jnp.uint8)
    return out_bytes.reshape(s, -1)[:, :L]
