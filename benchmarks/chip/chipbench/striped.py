"""HDFS's striped block layout, independent of the program.

An erasure-coding policy RS-k-m-<cell> stores a file's block group as
k data and m parity internal blocks.  The file's bytes are cut into
cells of ``cell`` bytes and dealt round-robin over the k data blocks,
one row (stripe) of k cells at a time; the last row may end part way
through a cell, and the cells after that end are empty.  Each parity
block holds, row by row, the parity of the row's data cells padded with
zeros to the row's first cell, so it is as long as data block 0
(Apache Hadoop, ``StripedBlockUtil.getInternalBlockLength``).  Built on
:class:`chipbench.reference.RS`; it imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference import RS


def last_cell_size(size: int, cell: int, k: int, idx: int) -> int:
    """Bytes of internal block ``idx``'s cell in a last row that holds
    ``size`` bytes (HDFS's ``lastCellSize``)."""
    if idx < k:
        size -= idx * cell
        if size < 0:
            size = 0
    return min(size, cell)


def internal_lengths(size: int, k: int, m: int, cell: int) -> list[int]:
    """Bytes of each of the k + m internal blocks of a block group that
    holds ``size`` bytes (HDFS's ``getInternalBlockLength``)."""
    row = cell * k
    last = size % row
    if last == 0:
        return [size // k] * (k + m)
    rows = (size - 1) // row + 1
    return [(rows - 1) * cell + last_cell_size(last, cell, k, i)
            for i in range(k + m)]


def rows(blob: np.ndarray, k: int, cell: int) -> list[list[np.ndarray]]:
    """The file's rows, each its k data cells (the empty ones of a last
    row included, as zero-length arrays)."""
    blob = np.asarray(blob, np.uint8).ravel()
    out = []
    for start in range(0, max(blob.size, 1), k * cell):
        out.append([blob[start + j * cell:start + (j + 1) * cell]
                    for j in range(k)])
    return out


def internal_blocks(blob: np.ndarray, rs: RS, cell: int) -> list[np.ndarray]:
    """The k + m internal blocks HDFS stores for ``blob``."""
    blocks: list[list[np.ndarray]] = [[] for _ in range(rs.k + rs.m)]
    for row in rows(blob, rs.k, cell):
        width = row[0].size
        even = width + width % 2        # the reference's word is 2 bytes
        padded = np.zeros((rs.k, even), np.uint8)
        for j, c in enumerate(row):
            padded[j, :c.size] = c
            blocks[j].append(c)
        for i, p in enumerate(rs.encode(padded)):
            blocks[rs.k + i].append(p[:width])
    return [np.concatenate(b) for b in blocks]
