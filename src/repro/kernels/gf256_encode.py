"""Bit-sliced GF(2^8) matmul Pallas kernel — the RS-encode hot spot.

The paper's payload handlers walk a 256x256-byte LUT per payload byte
(RISC-V: 5 instr/byte for RS(3,2), 7 for RS(6,3); Table II).  TPUs have no
efficient byte gather, so the kernel computes the *bit-sliced* form:

  GF(2^8) multiply-by-constant g is linear over GF(2)  =>  an 8x8
  bit-matrix M_g;  parity_plane[i, ob] = XOR_{j, ib} M[i,j,ob,ib] & data_plane[j, ib]

with bit-planes packed 32 codewords per uint32 lane.  One AND+XOR VPU op
therefore advances 32 bytes x lane-width of payload, vs. one byte per LUT
step — the TPU-native re-expression of the paper's per-packet encode loop.

Tiling: the word axis ``w`` is the minor (lane) dimension, tiled in
``block_w``-word VMEM blocks; the coefficients ride along each grid step
as a (k, 8, m*8, 1) tensor of 0x0/0xFFFFFFFF masks, one column per input
bit-plane.  Per grid step the kernel touches k*8*block_w*4 input bytes and
m*8*block_w*4 output bytes — with block_w=2048 and RS(6,3) that is
384 KiB in / 192 KiB out, inside VMEM, with the (8, 128)-aligned
(sublane, lane) layout the VPU wants.  The body only slices refs at
static indices: Mosaic has no general gather.

Validated in interpret mode against ``ref.gf_matmul_bitsliced_ref`` and the
byte-domain oracle across shape/dtype sweeps (tests/test_kernels.py), and
compiled for a TPU v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _masks(bitmat: jax.Array) -> jax.Array:
    """(m, k, 8_out, 8_in) 0/1 bit-matrices -> (k, 8_in, m*8_out, 1) masks.

    Each (chunk j, input bit ib) gets one column of 0x0/0xFFFFFFFF words,
    one row per output (parity i, bit ob): the kernel ANDs it, broadcast
    along lanes, with input plane (j, ib) broadcast along sublanes.  Built
    by XLA outside the kernel (it is tiny), so the kernel body only slices
    refs at static indices — Mosaic lowers no gather."""
    m, k = bitmat.shape[0], bitmat.shape[1]
    masks = jnp.uint32(0) - bitmat.astype(jnp.uint32)
    return masks.transpose(1, 3, 0, 2).reshape(k, 8, m * 8, 1)


def _gf_bitsliced_body(masks_ref, planes_ref, chunks) -> jax.Array:
    """(k, 8, block_w) planes x (k, 8, m*8, 1) masks -> (m*8, block_w).

    Output row (i, ob) is XOR_{j in chunks, ib} mask[j, ib, (i, ob)] &
    plane[j, ib]: 8 full-tile AND+XOR steps over (m*8, block_w) per chunk,
    with no fold and no relayout.  ``planes_ref`` is a (k, 8, block_w)
    view of the tile."""
    acc = None
    for j in chunks:
        masks, plane = masks_ref[j], planes_ref[j]  # (8, m*8, 1), (8, block_w)
        for ib in range(8):
            term = masks[ib] & plane[ib:ib + 1]
            acc = term if acc is None else acc ^ term
    return acc


def _gf_bitsliced_batched_kernel(masks_ref, planes_ref, out_ref, *, k: int):
    """One (stripe, word-block) grid step: (1, k, 8, block_w) -> (1, m*8, block_w)."""
    out_ref[0] = _gf_bitsliced_body(masks_ref, planes_ref.at[0], range(k))


@functools.partial(
    jax.jit, static_argnames=("m", "k", "block_w", "interpret")
)
def gf_matmul_bitsliced_batched(
    bitmat: jax.Array,
    planes: jax.Array,
    *,
    m: int,
    k: int,
    block_w: int = 1024,
    interpret: bool,
) -> jax.Array:
    """Batched bit-sliced GF(2^8) matmul: one dispatch for a stripe batch.

    A single Pallas call over a 2D (stripe, word-block) grid: every grid
    step encodes one ``block_w``-word tile of one stripe, so S concurrent
    stripes share one kernel launch, one coefficient upload, and one
    HBM->VMEM pipeline instead of S per-stripe dispatches.

    Args:
      bitmat: (m, k, 8, 8) uint32 0/1 coefficient bit-matrices (shared by
        every stripe in the batch).
      planes: (S, k, 8, w) uint32 input bit-planes; w % block_w == 0.
      m, k: static code dimensions.
      block_w: words per VMEM tile (lane-dim multiple of 128 on TPU).
      interpret: run the kernel body in Python on CPU (validation mode).

    Returns:
      (S, m, 8, w) uint32 output bit-planes.
    """
    s, kk, eight, w = planes.shape
    assert kk == k and eight == 8, planes.shape
    assert bitmat.shape == (m, k, 8, 8), bitmat.shape
    assert w % block_w == 0, (w, block_w)
    grid = (s, w // block_w)
    out = pl.pallas_call(
        functools.partial(_gf_bitsliced_batched_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 8, m * 8, 1), lambda si, wi: (0, 0, 0, 0)),
            pl.BlockSpec((1, k, 8, block_w), lambda si, wi: (si, 0, 0, wi)),
        ],
        out_specs=pl.BlockSpec((1, m * 8, block_w), lambda si, wi: (si, 0, wi)),
        out_shape=jax.ShapeDtypeStruct((s, m * 8, w), jnp.uint32),
        interpret=interpret,
        name="rs_gf_matmul",
    )(_masks(bitmat), planes)
    return out.reshape(s, m, 8, w)


def _gf_scale_kernel(masks_ref, planes_ref, out_ref, *, k: int):
    """One grid step of the stream-scaling (TriEC data-node) stage:
    out[j] = g[:, j] * chunk_j — the bit-sliced matmul body run once per
    chunk, *without* the fold over chunks, so every (parity, chunk)
    intermediate stream survives for downstream parity-node aggregation."""
    for j in range(k):
        out_ref[j] = _gf_bitsliced_body(masks_ref, planes_ref, (j,))


@functools.partial(
    jax.jit, static_argnames=("m", "k", "block_w", "interpret")
)
def gf_scale_bitsliced(
    bitmat: jax.Array,
    planes: jax.Array,
    *,
    m: int,
    k: int,
    block_w: int = 1024,
    interpret: bool,
) -> jax.Array:
    """Bit-sliced GF(2^8) constant-multiply of k chunks by an (m, k)
    coefficient grid: (k, 8, w) planes -> (m, k, 8, w) scaled streams.

    This is the data-node stage of the streaming TriEC dataflow (paper
    section VI-B1): each chunk j fans out to m intermediate-parity
    streams g[i, j] * chunk_j in one dispatch, without the k-fold the
    full matmul applies (the fold happens at the parity nodes).
    """
    kk, eight, w = planes.shape
    assert kk == k and eight == 8, planes.shape
    assert bitmat.shape == (m, k, 8, 8), bitmat.shape
    assert w % block_w == 0, (w, block_w)
    grid = (w // block_w,)
    out = pl.pallas_call(
        functools.partial(_gf_scale_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, 8, m * 8, 1), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((k, 8, block_w), lambda i: (0, 0, i)),
        ],
        out_specs=pl.BlockSpec((k, m * 8, block_w), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((k, m * 8, w), jnp.uint32),
        interpret=interpret,
    )(_masks(bitmat), planes)
    return out.reshape(k, m, 8, w).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# MXU variant: GF(2) matmul as int8 dot + parity (beyond-paper experiment).
# ---------------------------------------------------------------------------


def _gf_mxu_kernel(bigmat_ref, bits_ref, out_ref):
    """(8m, 8k) GF(2) matrix x (8k, block_n) bit columns -> (8m, block_n).

    GF(2) matmul == integer matmul followed by mod-2: routes the XOR
    accumulation through the MXU instead of the VPU.  Operands are int8
    bits; accumulation in int32 (max k*8 = 2048 < 2^31 safe).
    """
    acc = jnp.dot(
        bigmat_ref[...].astype(jnp.int8),
        bits_ref[...].astype(jnp.int8),
        preferred_element_type=jnp.int32,
    )
    out_ref[...] = (acc & 1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gf_matmul_mxu(
    bigmat: jax.Array,
    bits: jax.Array,
    *,
    block_n: int = 512,
    interpret: bool,
) -> jax.Array:
    """MXU-path GF(2) matmul: (8m, 8k) x (8k, n) -> (8m, n) over bits.

    ``bigmat`` is the block bit-matrix (rows = output bits, cols = input
    bits); ``bits`` holds one input bit per int8 element (unpacked).  The
    bit-unpack/pack happens outside (ops.py) — the kernel is pure matmul
    so XLA maps it onto the systolic array.
    """
    em, ek = bigmat.shape
    ek2, n = bits.shape
    assert ek == ek2, (bigmat.shape, bits.shape)
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        _gf_mxu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((em, ek), lambda i: (0, 0)),
            pl.BlockSpec((ek, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((em, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((em, n), jnp.int8),
        interpret=interpret,
    )(bigmat, bits)
