"""Erasure-coded files in the NameNode, held to HDFS's striped layout.

A directory with an ``ECPolicy`` makes every file created under it a
block group: k + m internal blocks on distinct nodes, with HDFS's
internal lengths and bytes.  Both are compared with the benchmark's
plain reference of HDFS striping (``benchmarks/chip/chipbench/
striped.py``, which imports nothing of the program), loaded as the chip
benchmark's tests load it.
"""

import hashlib
import itertools
import os
import sys

import numpy as np
import pytest

from repro.checkpoint.storage import StorageCluster
from repro.core import erasure
from repro.core.erasure import RSCode
from repro.core.packets import Resiliency
from repro.namenode import ECPolicy, NameNode

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "benchmarks", "chip")))

from chipbench import reference, striped  # noqa: E402

K, M, CELL = 6, 3, 256
POLICY = ECPolicy(K, M, CELL)
RS = reference.RS(K, M, 0x11D)
SIZES = [1, 3901, CELL - 1, CELL, K * CELL - 1, K * CELL, K * CELL + 1,
         5 * K * CELL // 2]


def ec_namenode(nodes: int = 12):
    cluster = StorageCluster(nodes, node_capacity=1 << 20)
    nn = NameNode(cluster)
    nn.mkdir("/ec")
    nn.set_ec_policy("/ec", POLICY)
    return cluster, nn


def write(nn, path: str, data: np.ndarray):
    nn.create(path)
    return nn.add_block(path, data)


def stored(cluster, nn, blk) -> list[np.ndarray]:
    """Each internal block as its node holds it (empty: no bytes)."""
    layout = nn._layouts[blk.object_id]
    coords = layout.data_coords + layout.parity_coords
    return [cluster.nodes[c.node].read(c.addr, n) if n else
            np.zeros(0, np.uint8) for c, n in zip(coords, layout.slot_lens)]


@pytest.mark.parametrize("size", SIZES)
def test_block_group_holds_hdfs_internal_blocks(size):
    cluster, nn = ec_namenode()
    data = np.random.default_rng(size).integers(0, 256, size, np.uint8)
    blk = write(nn, f"/ec/f{size}", data)
    want = striped.internal_blocks(data, RS, CELL)
    lens = striped.internal_lengths(size, K, M, CELL)
    assert [w.size for w in want] == lens
    assert blk.internal_lens == lens
    assert list(erasure.internal_lengths(size, K, M, CELL)) == lens
    assert blk.size == size and len(set(blk.placements)) == K + M
    got = stored(cluster, nn, blk)
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(g, w), i
    # an empty internal block takes no extent: each node's allocation is
    # the bytes of its non-empty internal blocks
    placed = [0] * cluster.num_nodes
    for node, n in zip(blk.placements, lens):
        placed[node] += n
    assert cluster.meta.placement.loads == placed
    assert nn.read_block(blk) == data.tobytes()


def test_one_encode_a_block_group(monkeypatch):
    """All rows of a 2.5-row file go through one batched encode."""
    calls = []
    orig = RSCode.encode_stripes

    def encode_stripes(self, data, backend=None):
        calls.append(np.asarray(data).shape)
        return orig(self, data, backend)

    monkeypatch.setattr(RSCode, "encode_stripes", encode_stripes)
    _, nn = ec_namenode()
    write(nn, "/ec/f", np.ones(5 * K * CELL // 2, np.uint8))
    assert calls == [(3, K, CELL)]


def test_block_group_on_the_kernels_matches_the_reference(monkeypatch):
    """The chip's Pallas kernels (interpreted on the CPU) encode a short
    and a multi-row file to HDFS's bytes."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "dataplane_backend",
                        lambda backend=None: backend or "jax")
    cluster, nn = ec_namenode()
    rng = np.random.default_rng(3)
    for size in (3901, 5 * K * CELL // 2):
        data = rng.integers(0, 256, size, np.uint8)
        blk = write(nn, f"/ec/k{size}", data)
        for g, w in zip(stored(cluster, nn, blk),
                        striped.internal_blocks(data, RS, CELL)):
            assert np.array_equal(g, w)


@pytest.fixture(scope="module")
def two_files():
    cluster, nn = ec_namenode()
    rng = np.random.default_rng(84)
    files = []
    for size in (100, 5 * K * CELL // 2):
        data = rng.integers(0, 256, size, np.uint8)
        files.append((write(nn, f"/ec/r{size}", data), data.tobytes()))
    return cluster, nn, files


@pytest.mark.parametrize("lost", list(itertools.combinations(range(K + M), M)))
def test_read_block_rebuilds_any_three_lost(two_files, lost):
    cluster, nn, files = two_files
    for blk, data in files:
        down = {blk.placements[i] for i in lost}
        for node in down:
            cluster.fail_node(node)
        try:
            assert nn.read_block(blk) == data
        finally:
            for node in down:
                cluster.failed.discard(node)
                cluster.router.heal(node)


def test_four_lost_is_unreadable(two_files):
    cluster, nn, files = two_files
    blk, _ = files[1]
    down = set(blk.placements[:M + 1])
    for node in down:
        cluster.fail_node(node)
    try:
        with pytest.raises(OSError):
            nn.read_block(blk)
    finally:
        for node in down:
            cluster.failed.discard(node)
            cluster.router.heal(node)


def test_whole_row_stores_what_write_object_bulk_stores():
    data = np.random.default_rng(6).integers(0, 256, K * CELL, np.uint8)
    a, nn = ec_namenode()
    blk = write(nn, "/ec/row", data)
    b = StorageCluster(12, node_capacity=1 << 20)
    layout = b.write_object_bulk([data], k=K, m=M)[0]
    la = nn._layouts[blk.object_id]
    coords = lambda lay: [(c.node, c.addr) for c in
                          lay.data_coords + lay.parity_coords]
    assert coords(la) == coords(layout)
    assert la.chunk_len == layout.chunk_len == CELL
    digest = lambda cl: hashlib.sha256(b"".join(
        n.storage.mem.tobytes() for n in cl.nodes)).hexdigest()
    assert digest(a) == digest(b)


def test_repair_rebuilds_internal_blocks_and_skips_empty_ones():
    cluster, nn = ec_namenode()
    rng = np.random.default_rng(9)
    files = []
    for i, size in enumerate((100, 3901, 5 * K * CELL // 2) * 4):
        data = rng.integers(0, 256, size, np.uint8)
        files.append((write(nn, f"/ec/p{i}", data), data))
    node = files[0][0].placements[0]
    cluster.fail_node(node)
    stats = cluster.repair_node(node)
    held = sum(1 for blk, _ in files for v, n in
               zip(blk.placements, blk.internal_lens) if v == node and n)
    assert stats["shards"] == held and stats["unrecoverable"] == 0
    for blk, data in files:
        for g, w in zip(stored(cluster, nn, blk),
                        striped.internal_blocks(data, RS, CELL)):
            assert np.array_equal(g, w)
    audit = cluster.audit()
    assert audit["readable_bytes"] == audit["bytes_written"]


def test_policy_is_inherited_by_files_created_under_it():
    _, nn = ec_namenode()
    wide = ECPolicy(3, 2, 1024)
    nn.mkdir("/ec/a/b")
    nn.mkdir("/plain")
    assert nn.create("/ec/a/b/f").ec_policy == POLICY
    nn.set_ec_policy("/ec/a", wide)
    assert nn.create("/ec/a/b/g").ec_policy == wide
    assert nn.lookup("/ec/a/b/f").ec_policy == POLICY   # kept its layout
    nn.set_ec_policy("/ec/a", None)
    assert nn.create("/ec/a/b/h").ec_policy == POLICY
    assert nn.create("/plain/f").ec_policy is None
    with pytest.raises(NotADirectoryError):
        nn.set_ec_policy("/plain/f", POLICY)


def test_replicated_files_are_as_before():
    cluster, nn = ec_namenode()
    nn.mkdir("/plain")
    nn.create("/plain/f", replication=3)
    data = bytes(range(200)) * 5
    blk = nn.add_block("/plain/f", data)
    layout = nn._layouts[blk.object_id]
    assert layout.resiliency == Resiliency.REPLICATION
    assert blk.internal_lens is None and len(blk.placements) == 3
    assert blk.placements == [c.node for c in layout.data_coords]
    assert nn.read_block(blk) == data
    assert (nn.block_groups, nn.internal_blocks) == (0, 0)
    assert nn.rpc_counts() == {"lookups": 1, "opens": 3, "commits": 1}
    # a detected failure queues the replicated block, never a block group
    ec = write(nn, "/ec/g", np.ones(10, np.uint8))
    dead = next(v for v in ec.placements if v in blk.placements)
    assert nn.replicator.mark_dead({dead}) == 1


def test_empty_file_has_no_block_group():
    _, nn = ec_namenode()
    nn.create("/ec/empty")
    with pytest.raises(ValueError):
        nn.add_block("/ec/empty", b"")
