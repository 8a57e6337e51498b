"""``op: repair``: the configuration's data set is written in set-up;
each operation fails node ``i`` (stepping through the nodes from a
seeded start) and rebuilds it in place with ``repair_node``.  The bytes
are those of the rebuilt shards."""

from chipbench.cells import DatasetCell

FAULTS = ("control", "unchanged_store", "unchanged_decode", "half_batch",
          "altered_answer")


class Kind(DatasetCell):
    def setup(self) -> None:
        self.write_dataset()
        nodes = self.config["nodes"]
        self.first = int(self.rng.integers(nodes))
        # warm-up: one repair of each node whose shards sit in a pattern
        # of slots no earlier node had, so the window repeats its shapes
        seen = set()
        with self.phase("warm-up"):
            for j in range(nodes):
                node = (self.first + j) % nodes
                slots = self.slots_on(node)
                sig = tuple(sorted((s, slots.count(s)) for s in set(slots)
                                   if s is not None))
                if sig not in seen:
                    seen.add(sig)
                    self.warm(self.repair, node)

    def repair(self, node: int) -> int:
        self.cluster.fail_node(node)
        stats = self.cluster.repair_node(node)
        if stats["unrecoverable"]:
            raise OSError(f"repair of node {node}: {stats}")
        return stats["bytes"]

    def op(self, i: int) -> int:
        return self.repair((self.first + i) % self.config["nodes"])

    def verify(self) -> None:
        audit = self.cluster.audit()
        self.count("audit_lost_bytes", audit["lost_bytes"])
        self.count("audit_unreadable_bytes",
                   audit["bytes_written"] - audit["readable_bytes"])
        self.check_objects(self.cluster, self.layouts,
                           lambda p: self.stripe(self.blobs[p]),
                           self.traffic["readback_objects"])
