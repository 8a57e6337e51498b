"""Plain Reed-Solomon reference over GF(2^8), independent of the program.

Built from the configuration's own statement of the code: the field's
primitive polynomial and the Cauchy parity matrix
``P[i][j] = 1 / ((k + i) XOR j)`` (the construction of HDFS's
``RSUtil.genCauchyMatrix`` and ISA-L's ``gf_gen_cauchy1_matrix``).
Multiplication is shift-and-add; encoding is a table walk over 16-bit
words (two bytes per lookup).  It imports nothing of the program and
takes no table the program made.
"""

from __future__ import annotations

import functools

import numpy as np


def gf_mul(a: int, b: int, poly: int) -> int:
    """Carry-less multiply of two field elements, reduced by ``poly``."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= poly
        b >>= 1
    return out


@functools.lru_cache(maxsize=4)
def mul_table(poly: int) -> np.ndarray:
    """(256, 256) products ``a * b`` in the field of ``poly``."""
    t = np.zeros((256, 256), np.uint8)
    for a in range(256):
        for b in range(256):
            t[a, b] = gf_mul(a, b, poly)
    t.flags.writeable = False
    return t


def gf_inv(a: int, poly: int) -> int:
    row = mul_table(poly)[a]
    hits = np.flatnonzero(row == 1)
    if a == 0 or hits.size == 0:
        raise ZeroDivisionError(f"{a} has no inverse")
    return int(hits[0])


def cauchy_parity(k: int, m: int, poly: int) -> np.ndarray:
    """(m, k) parity coefficients ``1 / ((k + i) XOR j)``."""
    return np.array([[gf_inv((k + i) ^ j, poly) for j in range(k)]
                     for i in range(m)], np.uint8)


@functools.lru_cache(maxsize=512)
def _word_table(c: int, poly: int) -> np.ndarray:
    """``c * x`` for every little-endian pair of bytes ``x``, as uint16."""
    row = mul_table(poly)[c].astype(np.uint16)
    x = np.arange(65536)
    t = row[x & 0xFF] | (row[x >> 8] << 8)
    t.flags.writeable = False
    return t


def gf_matmul(coeffs: np.ndarray, rows: np.ndarray, poly: int) -> np.ndarray:
    """(n, k) coefficients x (k, L) bytes -> (n, L) bytes, L even."""
    coeffs = np.asarray(coeffs, np.uint8)
    rows = np.ascontiguousarray(rows, np.uint8)
    words = rows.view(np.uint16)
    out = np.zeros((coeffs.shape[0], words.shape[1]), np.uint16)
    for i in range(coeffs.shape[0]):
        for j in range(coeffs.shape[1]):
            c = int(coeffs[i, j])
            if c:
                out[i] ^= np.take(_word_table(c, poly), words[j])
    return out.view(np.uint8)


def gf_mat_inv(a: np.ndarray, poly: int) -> np.ndarray:
    """Gauss-Jordan inverse of a square matrix over the field."""
    t = mul_table(poly)
    n = a.shape[0]
    aug = np.concatenate([np.asarray(a, np.uint8),
                          np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = col + int(np.flatnonzero(aug[col:, col])[0])
        aug[[col, piv]] = aug[[piv, col]]
        aug[col] = t[gf_inv(int(aug[col, col]), poly)][aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= t[aug[r, col]][aug[col]]
    return aug[:, n:]


def split(blob: np.ndarray, k: int, align: int = 32) -> np.ndarray:
    """A stripe's bytes as k zero-padded cells of a multiple of ``align``
    bytes: the layout the configuration's cells are stored in."""
    blob = np.asarray(blob, np.uint8).ravel()
    cell = -(-blob.size // k)
    cell = -(-cell // align) * align
    out = np.zeros(k * cell, np.uint8)
    out[:blob.size] = blob
    return out.reshape(k, cell)


class RS:
    """Systematic RS(k, m) over GF(2^8) with a Cauchy parity matrix."""

    def __init__(self, k: int, m: int, poly: int):
        self.k, self.m, self.poly = k, m, poly
        self.parity = cauchy_parity(k, m, poly)

    def encode(self, cells: np.ndarray) -> np.ndarray:
        """(k, L) data cells -> (m, L) parity cells."""
        return gf_matmul(self.parity, cells, self.poly)

    def decode(self, shards: list, rows: list[int]) -> np.ndarray:
        """(k, L) data cells from the k shards at ``rows`` (slots of the
        k + m; ``shards[i]`` holds slot ``rows[i]``)."""
        gen = np.concatenate([np.eye(self.k, dtype=np.uint8), self.parity])
        inv = gf_mat_inv(gen[rows], self.poly)
        return gf_matmul(inv, np.stack(shards), self.poly)
