"""Checkpoint plane: EC/replicated save-restore, degraded mode, healing."""

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.checkpoint.storage import StorageCluster
from repro.core.packets import ReplStrategy, Resiliency


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "layer0": {"w": rng.standard_normal((64, 128)).astype(np.float32),
                   "b": np.zeros(128, np.float32)},
        "emb": rng.integers(-5, 5, (32, 16)).astype(np.int32),
        "step": np.asarray(41),
    }


def _assert_tree_equal(a, b):
    assert np.array_equal(a["layer0"]["w"], b["layer0"]["w"])
    assert np.array_equal(a["layer0"]["b"], b["layer0"]["b"])
    assert np.array_equal(a["emb"], b["emb"])
    assert a["step"] == b["step"]


def test_ec_checkpoint_survives_m_failures():
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 23)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=2,
                                                      stripe_bytes=1 << 16))
    tree = _tree()
    mgr.save(10, tree, blocking=True)
    cluster.fail_node(1)
    cluster.fail_node(6)
    _assert_tree_equal(mgr.restore(10, treedef=tree), tree)


def test_ec_checkpoint_fails_beyond_m():
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 23)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=1,
                                                      stripe_bytes=1 << 16))
    tree = _tree(1)
    mgr.save(1, tree, blocking=True)
    cluster.fail_node(0)
    cluster.fail_node(1)
    cluster.fail_node(2)  # > m failures somewhere in the stripes
    with pytest.raises((ValueError, IOError)):
        mgr.restore(1, treedef=tree)


def test_heal_rebuilds_shards():
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 23)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=2,
                                                      stripe_bytes=1 << 16))
    tree = _tree(2)
    mgr.save(5, tree, blocking=True)
    cluster.fail_node(3)
    cluster.heal_node(3)            # rebuild from survivors
    cluster.fail_node(0)
    cluster.fail_node(1)            # two NEW failures; healed node must help
    _assert_tree_equal(mgr.restore(5, treedef=tree), tree)


def test_replicated_checkpoint_failover():
    cluster = StorageCluster(num_nodes=4)
    mgr = CheckpointManager(
        cluster,
        CheckpointPolicy(resiliency=Resiliency.REPLICATION, k=3,
                         strategy=ReplStrategy.PBT, stripe_bytes=1 << 16),
    )
    tree = _tree(3)
    mgr.save(2, tree, blocking=True)
    cluster.fail_node(0)
    cluster.fail_node(1)
    _assert_tree_equal(mgr.restore(2, treedef=tree), tree)


def test_multiple_steps_latest():
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 24)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=3, m=1,
                                                      stripe_bytes=1 << 16))
    t1, t2 = _tree(10), _tree(20)
    mgr.save(1, t1, blocking=True)
    mgr.save(2, t2, blocking=True)
    assert mgr.latest_step() == 2
    _assert_tree_equal(mgr.restore(treedef=t2), t2)
    _assert_tree_equal(mgr.restore(1, treedef=t1), t1)


def test_spill_and_reload_from_disk(tmp_path):
    """Cluster contents + namespace survive a process 'restart' via spill."""
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 20,
                             spill_dir=str(tmp_path / "spill"))
    blob = np.random.default_rng(0).integers(0, 256, 50_000, dtype=np.uint8)
    layout = cluster.write_object(blob.tobytes(), k=3, m=2)
    d = cluster.spill()

    revived = StorageCluster.from_spill(d)
    got = revived.read_object(revived.meta.lookup(layout.object_id))
    assert got == blob.tobytes()
    # degraded read still works after reload
    revived.fail_node(layout.data_coords[0].node)
    revived.fail_node(layout.parity_coords[0].node)
    assert revived.read_object(revived.meta.lookup(layout.object_id)) == \
        blob.tobytes()


# -- PolicySpec-routed EC: batched client encode (RSCode.encode_stripes) ----


def test_bulk_client_encode_matches_nic_streaming_path():
    """encode='client' (one batched RSCode.encode_stripes per leaf) must
    lay out byte-identical shards to the per-packet NIC streaming path."""
    blob = np.random.default_rng(7).integers(0, 256, 70_001, dtype=np.uint8)
    a = StorageCluster(num_nodes=6, node_capacity=1 << 22)
    b = StorageCluster(num_nodes=6, node_capacity=1 << 22)
    la = a.write_object_bulk([blob.tobytes()], k=3, m=2)[0]
    lb = b.write_object(blob.tobytes(), k=3, m=2)  # NIC streaming EC
    assert la.chunk_len == lb.chunk_len
    for ca, cb in zip(
        list(la.data_coords) + list(la.parity_coords),
        list(lb.data_coords) + list(lb.parity_coords),
    ):
        sa = a.nodes[ca.node].read(ca.addr, la.chunk_len)
        sb = b.nodes[cb.node].read(cb.addr, lb.chunk_len)
        assert np.array_equal(sa, sb)
    assert a.read_object(la) == blob.tobytes()


def test_bulk_encode_roundtrip_under_erasures():
    """ROADMAP item: encode_stripes wired into checkpoint EC — the bulk
    path must survive m node losses end to end."""
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 23)
    mgr = CheckpointManager(
        cluster,
        CheckpointPolicy(k=4, m=2, stripe_bytes=1 << 15, encode="client"),
    )
    tree = _tree(9)
    mgr.save(3, tree, blocking=True)
    cluster.fail_node(2)
    cluster.fail_node(5)
    _assert_tree_equal(mgr.restore(3, treedef=tree), tree)
    # beyond m failures the stripe must be unrecoverable
    cluster.fail_node(0)
    cluster.fail_node(1)
    with pytest.raises((ValueError, IOError)):
        mgr.restore(3, treedef=tree)


def test_manager_accepts_policy_spec():
    """CheckpointManager lowers a declarative PolicySpec directly."""
    from repro.policy import PolicySpec, RS, SpongeAuth

    spec = PolicySpec("spin", SpongeAuth(), erasure=RS(3, 2, "client"))
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 23)
    mgr = CheckpointManager(cluster, spec)
    assert mgr.policy.k == 3 and mgr.policy.m == 2
    assert mgr.policy.encode == "client"
    tree = _tree(11)
    mgr.save(1, tree, blocking=True)
    cluster.fail_node(1)
    _assert_tree_equal(mgr.restore(1, treedef=tree), tree)


def test_checkpoint_policy_spec_roundtrip():
    for pol in (
        CheckpointPolicy(k=5, m=3, encode="client"),
        CheckpointPolicy(k=4, m=2, encode="nic"),
        CheckpointPolicy(resiliency=Resiliency.REPLICATION, k=3,
                         strategy=ReplStrategy.PBT),
    ):
        back = CheckpointPolicy.from_spec(pol.spec(),
                                          stripe_bytes=pol.stripe_bytes)
        assert back.resiliency == pol.resiliency
        assert back.k == pol.k
        if pol.resiliency == Resiliency.ERASURE_CODING:
            assert back.m == pol.m and back.encode == pol.encode
        else:
            assert back.strategy == pol.strategy


@pytest.mark.parametrize("offset", [0, 37, 63])
def test_restore_detects_altered_leaf_bytes(offset):
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 23)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=2,
                                                      stripe_bytes=1 << 16))
    tree = _tree()
    mgr.save(3, tree, blocking=True)
    (leaf,) = [lf for lf in mgr._manifests[3]["leaves"]
               if lf["path"] == "layer0/w"]
    layout = cluster.meta.lookup(leaf["stripes"][0]["oid"])
    shard, at = divmod(offset, layout.chunk_len)
    coord = layout.data_coords[shard]
    cluster.nodes[coord.node].storage.mem[coord.addr + at] ^= 0x5A
    with pytest.raises(IOError, match="integrity check failed for layer0/w"):
        mgr.restore(3)
