"""The mdtest cell's check over a window that moves to a fresh cluster:
a wrong byte and a stray file in a retired cluster are still found."""

from chipbench import cells


def test_mdtest_cell_checks_every_retired_cluster(run_cell, monkeypatch):
    """The tiny mdtest cluster fills after 12 files: the window goes on
    in a fresh cluster with an empty directory, and a parity byte planted
    wrong in the first cluster, and a stray file in its directory, are
    still found."""
    seen = []
    kind = cells.kind("mdtest").Kind
    orig = kind.rotate

    def rotate(self):
        if not seen:
            blk = self.block(self.files[0])
            layout = self.cluster.meta.lookup(blk.object_id)
            coord = layout.parity_coords[0]
            self.cluster.nodes[coord.node].storage.mem[coord.addr] ^= 1
            self.nn.create(f"{self.dir}/stray")
        seen.append((len(self.files), self.nn.listdir(self.dir)))
        orig(self)
        assert self.nn.listdir(self.dir) == []

    monkeypatch.setattr(kind, "rotate", rotate)
    result, _, _ = run_cell("io500-hdfs-rs6-3.mdtest-hard-write", seed=3,
                            seconds=0.5)
    assert seen and seen[0][0] == 12 and len(seen[0][1]) == 13
    assert not result["correct"]
    checks = {n: c["value"] for n, c in result["checks"].items()}
    assert checks["stored_shards_wrong"] == 1
    assert checks["namespace_wrong"] == 1
