"""``op: mdtest``: IO500's mdtest-hard write phase through the NameNode.

Set-up builds a cluster of the configuration's nodes, a ``NameNode``
over it and the shared ``directory``, with the configuration's
erasure-coding policy set on it (HDFS's ``setErasureCodingPolicy``);
it writes one warm-up file of each size (every shape of the window),
then opens the window on a fresh cluster whose directory is empty.
Operation ``i`` creates ``file.mdtest.0.<i>`` there (rank 0, item i,
as mdtest names its files), writes its ``file_bytes`` bytes as one
block group (``NameNode.add_block``) and closes it.

The files' bytes come from a pool of ``pool_files`` made from the seed
on the device, in a seeded order, and every file is made unique by its
serial number, as in ``kinds/write.py``.  ``file_bytes`` may list
sizes, which the files take in turn.

The check, against :mod:`chipbench.striped`: every internal block of
every acknowledged file as its node holds it, every node's allocated
bytes those of its non-empty internal blocks (an empty one takes no
extent); a seeded sample of files, the last among them, read back
through ``NameNode.read_block``; after the window, m seeded nodes
failed, among them the one holding the last file's data block 0, and
a seeded sample of the files whose data block 0 they held read back,
rebuilt by the decoder; and the directory, which lists exactly the
acknowledged files, each with its length in one block group.
"""

import numpy as np

from chipbench import cells, faults, striped
from chipbench.cells import Cell, make_blobs

FAULTS = faults.NAMES
_write = cells.kind("write")


class Kind(Cell):
    def setup(self) -> None:
        from repro.namenode import ECPolicy

        t = self.traffic
        self.cell_bytes = self.config["cell_bytes"]
        self.policy = ECPolicy(self.k, self.m, self.cell_bytes)
        self.sizes = [int(n) for n in np.atleast_1d(t["file_bytes"])]
        self.lens = {n: striped.internal_lengths(n, self.k, self.m,
                                                 self.cell_bytes)
                     for n in self.sizes}
        stored = max(sum(lens) for lens in self.lens.values())
        self.max_files = max(1, t["cluster_bytes"] // stored)
        with self.phase("files made on the device"):
            self.pool = np.array(make_blobs(self.seed, t["pool_files"],
                                            max(self.sizes)))
        self.order = self.rng.permutation(t["pool_files"])
        self.serial = 0
        with self.phase("warm-up"):
            self.fresh()
            for n in sorted(set(self.sizes)):
                self.warm(self.make_file, f"warm-up.{n}", n)
            self.fresh()

    def fresh(self) -> None:
        """A new cluster and NameNode, with the shared directory empty:
        round-robin placement gives each node at most its share of the
        non-empty internal blocks, plus one placement cycle of slack."""
        from repro.checkpoint.storage import StorageCluster
        from repro.namenode import NameNode

        nodes = self.config["nodes"]
        blocks = max(sum(1 for n in lens if n) for lens in self.lens.values())
        longest = max(max(lens) for lens in self.lens.values())
        per_node = -(-self.max_files * blocks // nodes) + self.k + self.m
        self.cluster = StorageCluster(nodes, node_capacity=per_node * longest)
        self.nn = NameNode(self.cluster)
        self.dir = self.config["directory"]
        self.nn.mkdir(self.dir)
        self.nn.set_ec_policy(self.dir, self.policy)
        #: (name, pool index, serial, size) of every acknowledged file
        self.files: list = []

    def make_file(self, name: str, size: int) -> int:
        """mdtest's create, write and close of one file."""
        idx = int(self.order[self.serial % len(self.order)])
        self.serial += 1
        blob, tag = self.pool[idx], _write.tag_bytes(self.serial)
        blob[:_write.TAG_BYTES] ^= tag
        try:
            path = f"{self.dir}/{name}"
            self.nn.create(path)
            self.nn.add_block(path, blob[:size])
        finally:
            blob[:_write.TAG_BYTES] ^= tag
        self.files.append((name, idx, self.serial, size))
        return size

    def op(self, i: int) -> int:
        return self.make_file(f"file.mdtest.0.{i}",
                              self.sizes[i % len(self.sizes)])

    def full(self) -> bool:
        return len(self.files) >= self.max_files

    def rotate(self) -> None:
        self.verify()
        self.fresh()

    def counters(self):
        from repro.trace import namenode_registry

        return namenode_registry(self.nn)

    def file_bytes(self, entry) -> np.ndarray:
        _, idx, serial, size = entry
        blob = self.pool[idx].copy()
        blob[:_write.TAG_BYTES] ^= _write.tag_bytes(serial)
        return blob[:size]

    def inode(self, entry):
        """The NameNode's inode of an acknowledged file, or None."""
        try:
            return self.nn.namespace.lookup(f"{self.dir}/{entry[0]}")
        except FileNotFoundError:
            return None

    def block(self, entry):
        """The block group of an acknowledged file, or None when the file
        does not have exactly one."""
        f = self.inode(entry)
        return f.blocks[0] if f is not None and len(f.blocks) == 1 else None

    def verify(self) -> None:
        if not self.files:
            return
        self.check_stored()
        self.check_reads()
        self.check_namespace()

    def check_stored(self) -> None:
        cluster = self.cluster
        bad = 0
        placed = np.zeros(len(cluster.nodes), np.int64)
        for entry in self.files:
            want = striped.internal_blocks(self.file_bytes(entry), self.ref,
                                           self.cell_bytes)
            blk = self.block(entry)
            if blk is None:
                bad += len(want)
                continue
            layout = cluster.meta.lookup(blk.object_id)
            coords = list(layout.data_coords) + list(layout.parity_coords)
            for coord, length, w in zip(coords, layout.slot_lens, want):
                if length != w.size:
                    bad += 1
                elif w.size:
                    got = cluster.nodes[coord.node].read(coord.addr, w.size)
                    bad += not np.array_equal(got, w)
                    placed[coord.node] += w.size
        # no extent beyond the non-empty internal blocks
        bad += int(np.sum(placed != np.asarray(cluster.meta.placement.loads)))
        self.count("stored_shards_wrong", bad)

    def read_back(self, entries) -> int:
        """Files of ``entries`` that ``NameNode.read_block`` does not give
        back whole."""
        bad = 0
        for entry in entries:
            blk = self.block(entry)
            try:
                got = self.nn.read_block(blk) if blk is not None else None
            except OSError:
                got = None
            bad += got != self.file_bytes(entry).tobytes()
        return bad

    def check_reads(self) -> None:
        t, n = self.traffic, len(self.files)
        picks = self.rng.choice(n - 1, min(t["readback_files"], n) - 1,
                                replace=False) if n > 1 else []
        entries = [self.files[p] for p in sorted(picks)] + [self.files[-1]]
        self.count("readback_shards_wrong", self.read_back(entries))
        # m nodes down, the last file's data block 0 on one of them
        first = self.block(self.files[-1])
        nodes = self.config["nodes"]
        down = {first.placements[0]} if first is not None else set()
        others = [v for v in range(nodes) if v not in down]
        down |= {int(v) for v in self.rng.choice(others, self.m - len(down),
                                                 replace=False)}
        for node in sorted(down):
            self.cluster.fail_node(node)
        hit = [e for e in self.files
               if (b := self.block(e)) is not None and b.placements[0] in down]
        picks = self.rng.choice(len(hit), min(t["degraded_files"], len(hit)),
                                replace=False)
        self.count("degraded_reads_wrong",
                   self.read_back([hit[p] for p in sorted(picks)]))

    def check_namespace(self) -> None:
        bad = len(set(self.nn.listdir(self.dir))
                  ^ {e[0] for e in self.files})
        for entry in self.files:
            blk = self.block(entry)
            bad += (blk is None or self.inode(entry).size != entry[3]
                    or blk.internal_lens != self.lens[entry[3]]
                    or len(set(blk.placements)) != self.k + self.m)
        self.count("namespace_wrong", bad)
