"""``op: read``: the configuration's data set is written in set-up, then
``failed_nodes`` consecutive nodes from a seeded start fail; each
operation reads one object drawn uniformly from the seed with
``read_objects([layout])`` (verify on).  The bytes of a seeded share of
the reads are kept and compared with the data set once the window has
closed."""

from chipbench.cells import DatasetCell

FAULTS = ("control", "unchanged_decode", "half_batch", "altered_answer")


class Kind(DatasetCell):
    def setup(self) -> None:
        t = self.traffic
        self.write_dataset()
        nodes = self.config["nodes"]
        first = int(self.rng.integers(nodes))
        self.failed = [(first + j) % nodes for j in range(t["failed_nodes"])]
        for node in self.failed:
            self.cluster.fail_node(node)
        self.order = self.rng.integers(len(self.blobs), size=1 << 16)
        self.keep = self.rng.random(1 << 16) < t["kept_share"]
        self.kept: list = []
        # warm-up: one read of each erasure pattern the failed nodes leave
        slots = [self.slots_on(n) for n in self.failed]
        patterns: dict = {}
        for idx in range(len(self.layouts)):
            patterns.setdefault(tuple(s[idx] for s in slots), idx)
        with self.phase("warm-up"):
            for idx in patterns.values():
                self.warm(self.cluster.read_objects, [self.layouts[idx]])

    def op(self, i: int) -> int:
        idx = int(self.order[i % len(self.order)])
        got = self.cluster.read_objects([self.layouts[idx]])[0]
        if len(got) != self.object_bytes:
            raise OSError(f"short read: {len(got)} of {self.object_bytes} B")
        if self.keep[i % len(self.keep)] and len(self.kept) < \
                self.traffic["kept_results"]:
            self.kept.append((idx, got))
        return self.object_bytes

    def verify(self) -> None:
        bad = sum(got != self.blobs[idx].tobytes() for idx, got in self.kept)
        self.count("reads_wrong", bad)
