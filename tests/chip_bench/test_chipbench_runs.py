"""Whole runs of every cell at a tiny size on the CPU: the result line,
the checks beside their limits, the traced run's per-layer metrics, and
the refusal to run without a TPU."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness

CELLS = [w["name"] for w in harness.load_bench()["workloads"]]
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_is_correct_and_reports_its_end_to_end_metrics(
        run_cell, cell):
    result, out, err = run_cell(cell, seed=2**31 + 11)
    assert json.loads(out[-1]) == result
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    bench = harness.load_bench()
    want = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
    assert set(result["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    for name, m in result["metrics"].items():
        assert m["value"] > 0, name
    for name, c in result["checks"].items():
        assert c == {"value": 0, "limit": 0}, name
        assert f"check {name}: 0 (limit 0)" in err
    assert err.rstrip().splitlines()[-1].startswith("check failed_operations")
    assert "compiles inside it: 0" in "\n".join(out)
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_host_side_layer_metrics(run_cell, cell):
    result, out, _ = run_cell(cell, seed=5, trace=1)
    assert result["correct"]
    bench = harness.load_bench()
    declared = {m["name"]: m for m in harness.cell_metrics(bench, cell, True)}
    got = set(result["metrics"])
    assert got <= set(declared)
    # the CPU has no device trace: only the host side's metrics read,
    # from the benchmark's spans and from the program's spans and counters
    assert {n.split(".")[0] for n in got} >= {"packet_plane_share",
                                               "codec_share", "packet_us"}
    assert not any(n.startswith(("codec_roofline", "device_idle",
                                 "codec_kernel_share", "codec_host_share"))
                   for n in got)
    for name in got:
        value = result["metrics"][name]["value"]
        assert value > 0, name
        if declared[name]["unit"] == "%":
            assert value <= 100, name
    if cell == "ckpt-rs6-3.save":
        assert {"ckpt_self_share", "ckpt_snapshot_share"} <= got
    assert any(line.startswith("program spans: ") and
               line.endswith(" kept, 0 dropped") for line in out)
    assert out[-2].startswith("counters per operation")
    assert "packets.to_nodes" in out[-2]


def test_write_cell_checks_every_retired_cluster(run_cell, monkeypatch):
    """The tiny stream-write cluster fills after 12 objects: the window
    goes on in a fresh cluster, and a write planted wrong in the first
    cluster is still found."""
    from chipbench import cells

    seen = []
    kind = cells.kind("write").Kind
    orig = kind.rotate

    def rotate(self):
        if not seen:
            layout = self.entries[0][0]
            coord = layout.parity_coords[0]
            node = self.cluster.nodes[coord.node]
            node.storage.mem[coord.addr] ^= 1
        seen.append(len(self.entries))
        orig(self)

    monkeypatch.setattr(kind, "rotate", rotate)
    result, out, _ = run_cell("hdfs-rs6-3-1m.stream-write", seed=3,
                              seconds=0.5)
    assert seen and seen[0] == 12
    assert not result["correct"]
    assert result["checks"]["stored_shards_wrong"]["value"] == 1


def test_run_without_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "chip", "run.py"),
         "--workload", "hdfs-rs6-3-1m.stream-write", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "needs 1 TPU chip(s)" in proc.stderr


def test_every_write_carries_bytes_no_other_write_had(run_cell, monkeypatch):
    """A pool of 4 objects feeds many writes, and each is still unique: a
    cache keyed by content finds nothing to reuse."""
    from repro.checkpoint.storage import StorageCluster

    seen = []
    orig = StorageCluster.write_object_bulk

    def write_object_bulk(self, blobs, *args, **kwargs):
        seen.extend(hashlib.sha256(np.asarray(b).tobytes()).digest()
                    for b in blobs)
        return orig(self, blobs, *args, **kwargs)

    monkeypatch.setattr(StorageCluster, "write_object_bulk",
                        write_object_bulk)
    result, _, _ = run_cell("hdfs-rs6-3-1m.stream-write", seed=8)
    assert result["correct"]
    assert len(seen) > 4 and len(set(seen)) == len(seen)


def test_save_check_compares_stored_parity_with_the_reference(
        run_cell, monkeypatch):
    """One byte of a parity shard of the last save's largest object,
    changed as it lies on its node, is found by the save's check (once
    for each cluster checked: the tiny one fills and is retired)."""
    from chipbench import cells

    kind = cells.kind("save").Kind
    orig = kind.verify
    planted = []

    def verify(self):
        manifest = self.mgr._manifests[self.steps[-1]]
        oids = [(s["size"], s["oid"]) for leaf in manifest["leaves"]
                for s in leaf["stripes"]]
        layout = self.cluster.meta.lookup(max(oids)[1])
        coord = layout.parity_coords[-1]
        self.cluster.nodes[coord.node].storage.mem[coord.addr] ^= 1
        planted.append(coord)
        orig(self)

    monkeypatch.setattr(kind, "verify", verify)
    result, _, _ = run_cell("ckpt-rs6-3.save", seed=9)
    assert not result["correct"]
    assert planted
    assert result["checks"]["stored_shards_wrong"]["value"] == len(planted)
