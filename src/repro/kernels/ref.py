"""Pure-jnp oracles for the Pallas kernels.

These implement the NIC/CPU-idiomatic algorithms (table-walk GF(2^8)
multiplication, straight XOR folds) with plain jnp ops — slow but obviously
correct, validated against ``repro.core.gf256`` numpy code and used as the
assert_allclose reference for every kernel shape/dtype sweep.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import gf256

# Device-resident log/antilog tables (the paper's LUT approach).
_EXP = jnp.asarray(gf256.EXP_TABLE)            # (512,) uint8
_LOG = jnp.asarray(gf256.LOG_TABLE)            # (256,) int32


def gf_mul_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Elementwise GF(2^8) multiply via table gathers (broadcasting)."""
    a = a.astype(jnp.uint8)
    b = b.astype(jnp.uint8)
    logs = _LOG[a.astype(jnp.int32)] + _LOG[b.astype(jnp.int32)]
    out = _EXP[logs]
    return jnp.where((a == 0) | (b == 0), jnp.uint8(0), out)


def gf_matmul_batched_ref(coeffs: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Stripe-batched LUT-path matmul: (n, k) x (S, k, L) -> (S, n, L).

    XOR-accumulated table-walk products — the per-byte loop the paper's
    payload handlers run on the NIC (5-7 instructions/byte), vectorized.
    """
    coeffs = coeffs.astype(jnp.uint8)
    data = data.astype(jnp.uint8)
    prods = gf_mul_ref(coeffs[None, :, :, None], data[:, None, :, :])  # (S, n, k, L)
    out = prods[:, :, 0, :]
    for j in range(1, data.shape[1]):
        out = out ^ prods[:, :, j, :]
    return out


def xor_reduce_ref(x: jnp.ndarray) -> jnp.ndarray:
    """XOR-fold over axis 0 (accumulator-pool aggregation oracle)."""
    out = x[0]
    for i in range(1, x.shape[0]):
        out = out ^ x[i]
    return out


# -- bit-plane helpers (jnp mirrors of core.gf256) ---------------------------


def pack_bitplanes(data: jnp.ndarray) -> jnp.ndarray:
    """(..., n) uint8 -> (..., 8, n//32) uint32; n % 32 == 0."""
    n = data.shape[-1]
    assert n % 32 == 0, n
    words = data.reshape(*data.shape[:-1], n // 32, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    planes = []
    for b in range(8):
        bit = (words >> jnp.uint32(b)) & jnp.uint32(1)
        planes.append((bit << shifts).sum(axis=-1, dtype=jnp.uint32))
    return jnp.stack(planes, axis=-2)


def unpack_bitplanes(planes: jnp.ndarray) -> jnp.ndarray:
    """(..., 8, w) uint32 -> (..., 32*w) uint8."""
    w = planes.shape[-1]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    out = jnp.zeros(planes.shape[:-2] + (w, 32), dtype=jnp.uint8)
    for b in range(8):
        bits = (planes[..., b, :, None] >> shifts) & jnp.uint32(1)
        out = out | (bits.astype(jnp.uint8) << np.uint8(b))
    return out.reshape(*planes.shape[:-2], w * 32)


def gf_matmul_bitsliced_ref(bitmat: jnp.ndarray, planes: jnp.ndarray) -> jnp.ndarray:
    """Bit-sliced GF matmul oracle (jnp, unfused).

    bitmat: (m, k, 8, 8) uint8 bit-matrices (out-bit, in-bit) per coefficient;
    planes: (k, 8, w) uint32 input bit-planes -> (m, 8, w) output planes.
    Mirrors exactly what the Pallas kernel computes, for A/B validation.
    """
    m, k = bitmat.shape[0], bitmat.shape[1]
    w = planes.shape[-1]
    out = jnp.zeros((m, 8, w), dtype=jnp.uint32)
    for i in range(m):
        for ob in range(8):
            acc = jnp.zeros((w,), dtype=jnp.uint32)
            for j in range(k):
                for ib in range(8):
                    bit = bitmat[i, j, ob, ib].astype(jnp.uint32)
                    mask = jnp.uint32(0) - bit  # 0x0 or 0xFFFFFFFF
                    acc = acc ^ (planes[j, ib] & mask)
            out = out.at[i, ob].set(acc)
    return out
