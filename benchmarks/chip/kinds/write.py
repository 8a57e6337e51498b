"""``op: write``: each operation writes ``objects_per_op`` objects of
``object_bytes`` bytes through ``StorageCluster.write_object_bulk``.

The objects come from a pool of ``pool_objects`` made from the seed in
set-up, in a seeded order, and every object written is made unique: its
first 8 bytes (the head of data cell 0) are XORed with the object's
serial number, so no two writes of a run carry the same bytes and a
cache keyed by content finds nothing to reuse.  The reference's parity
of such an object is the pool object's parity plus that of the 8-byte
change.
"""

import numpy as np

from chipbench.cells import Cell, make_blobs

FAULTS = ("control", "unchanged_store", "half_batch", "altered_answer")
#: bytes at the head of each object that carry its serial number
TAG_BYTES = 8


def tag_bytes(serial: int) -> np.ndarray:
    return np.frombuffer(int(serial).to_bytes(TAG_BYTES, "little"), np.uint8)


class Kind(Cell):
    def setup(self) -> None:
        t = self.traffic
        self.object_bytes = t["object_bytes"]
        self.per_op = t["objects_per_op"]
        if not self.per_op <= t["pool_objects"]:
            raise ValueError("objects_per_op is more than the pool holds")
        with self.phase("objects made on the device"):
            self.pool = np.array(make_blobs(self.seed, t["pool_objects"],
                                            self.object_bytes))
        self.order = self.rng.permutation(t["pool_objects"])
        stored = (self.k + self.m) * self.chunk(self.object_bytes)
        self.max_objects = max(self.per_op, t["cluster_bytes"] // stored)
        self.serial = 0
        self.pool_shards: dict = {}
        self.cluster = self.new_cluster(self.max_objects, self.object_bytes)
        #: (layout, pool index, serial) of every object in the cluster
        self.entries: list = []
        with self.phase("warm-up"):
            self.warm(self.op, -1)      # every shape of the window

    def op(self, i: int) -> int:
        n = len(self.order)
        idxs = [int(self.order[(i * self.per_op + j) % n])
                for j in range(self.per_op)]
        serials = range(self.serial + 1, self.serial + 1 + self.per_op)
        self.serial += self.per_op
        blobs = [self.pool[j] for j in idxs]
        for blob, s in zip(blobs, serials):
            blob[:TAG_BYTES] ^= tag_bytes(s)
        try:
            layouts = self.cluster.write_object_bulk(blobs, k=self.k,
                                                     m=self.m)
        finally:
            for blob, s in zip(blobs, serials):
                blob[:TAG_BYTES] ^= tag_bytes(s)
        self.entries += list(zip(layouts, idxs, serials))
        return self.per_op * self.object_bytes

    def full(self) -> bool:
        return len(self.entries) + self.per_op > self.max_objects

    def rotate(self) -> None:
        self.verify()
        self.cluster = self.new_cluster(self.max_objects, self.object_bytes)
        self.entries = []

    def want(self, p: int) -> np.ndarray:
        """The k + m shards of entry ``p``, by the reference."""
        _, idx, serial = self.entries[p]
        if idx not in self.pool_shards:
            self.pool_shards[idx] = self.stripe(self.pool[idx])
        out = self.pool_shards[idx].copy()
        delta = np.zeros((self.k, TAG_BYTES), np.uint8)
        delta[0] = tag_bytes(serial)
        out[0, :TAG_BYTES] ^= delta[0]
        out[self.k:, :TAG_BYTES] ^= self.ref.encode(delta)
        return out

    def verify(self) -> None:
        self.check_objects(self.cluster, [e[0] for e in self.entries],
                           self.want, self.traffic["readback_objects"])
