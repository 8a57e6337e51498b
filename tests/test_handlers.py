"""Functional DFS integration: Listing-1 handlers end to end."""

import dataclasses

import numpy as np
import pytest

from repro.core.auth import CapabilityAuthority, Rights
from repro.core.erasure import RSCode, split_stripe
from repro.core.handlers import DFSClient, DFSNode, Router
from repro.core.packets import (
    DFSHeader,
    OpType,
    ReplicaCoord,
    ReplStrategy,
    Resiliency,
    WriteRequestHeader,
    packetize_write,
)


@pytest.fixture
def cluster():
    auth = CapabilityAuthority(b"0123456789abcdef")
    router = Router()
    nodes = [DFSNode(i, router, auth) for i in range(8)]
    client = DFSClient(client_id=5, router=router)
    cap = auth.issue(client_id=5, object_id=1, offset=0, length=1 << 24,
                     rights=Rights.WRITE, expiry=10**10)
    return auth, router, nodes, client, cap


def test_raw_write_lands_and_acks(cluster):
    _, router, nodes, client, cap = cluster
    data = np.random.default_rng(0).integers(0, 256, 5000, dtype=np.uint8)
    greqs = client.write(cap, data, [ReplicaCoord(0, 1000)])
    acks = client.acks()
    assert len(acks) == 1 and acks[0].ctrl == OpType.WRITE_ACK
    assert acks[0].greq_id == greqs[0]
    assert np.array_equal(nodes[0].read(1000, 5000), data)


@pytest.mark.parametrize("strategy,k", [
    (ReplStrategy.RING, 2), (ReplStrategy.RING, 4),
    (ReplStrategy.PBT, 5), (ReplStrategy.PBT, 7),
])
def test_replication_all_replicas_durable(cluster, strategy, k):
    _, router, nodes, client, cap = cluster
    data = np.random.default_rng(1).integers(0, 256, 9000, dtype=np.uint8)
    targets = [ReplicaCoord(i, 2000) for i in range(k)]
    client.write(cap, data, targets, resiliency=Resiliency.REPLICATION,
                 strategy=strategy)
    acks = client.acks()
    # durable ack: exactly one, sent only after every replica holds the data
    assert len(acks) == 1 and acks[0].ctrl == OpType.WRITE_ACK
    for i in range(k):
        assert np.array_equal(nodes[i].read(2000, 9000), data), f"replica {i}"


def test_erasure_coded_write_parities_and_decode(cluster):
    _, router, nodes, client, cap = cluster
    data = np.random.default_rng(2).integers(0, 256, 10000, dtype=np.uint8)
    dtargets = [ReplicaCoord(i, 60000) for i in range(3)]
    ptargets = [ReplicaCoord(3, 60000), ReplicaCoord(4, 60000)]
    greqs = client.write(cap, data, dtargets,
                         resiliency=Resiliency.ERASURE_CODING, ec_m=2,
                         parity_targets=ptargets)
    acks = client.acks()
    assert len(acks) == 5                    # 3 data + 2 parity(stripe) acks
    assert len([a for a in acks if a.greq_id == greqs[0]]) == 2
    code = RSCode(3, 2)
    chunks = split_stripe(data, 3)
    L = chunks.shape[1]
    assert np.array_equal(
        np.stack([nodes[i].read(60000, L) for i in range(3)]), chunks
    )
    parity = code.encode(chunks)
    for i in range(2):
        assert np.array_equal(nodes[3 + i].read(60000, L), parity[i])
    # stripe survives any 2 losses
    rec = code.decode([None, chunks[1], None, parity[0], parity[1]])
    assert np.array_equal(rec, chunks)


def test_forged_capability_nacked_no_write(cluster):
    _, router, nodes, client, cap = cluster
    bad = dataclasses.replace(cap, rights=int(Rights.ADMIN | Rights.WRITE))
    before = nodes[6].storage.bytes_written
    data = np.zeros(100, np.uint8)
    client.write(bad, data, [ReplicaCoord(6, 0)])
    acks = client.acks()
    assert acks[-1].ctrl == OpType.NACK
    assert nodes[6].storage.bytes_written == before


@pytest.mark.parametrize("forgery", ["tag_word0_bit0", "tag_word1_bit31",
                                     "write_only"])
def test_forged_capability_read_nacked_no_data(cluster, forgery):
    auth, router, nodes, client, cap = cluster
    data = np.random.default_rng(3).integers(0, 256, 5000, dtype=np.uint8)
    client.write(cap, data, [ReplicaCoord(2, 500)])
    good = auth.issue(client_id=5, object_id=1, offset=0, length=1 << 24,
                      rights=Rights.READ | Rights.WRITE, expiry=10**10)
    if forgery == "tag_word0_bit0":
        bad = dataclasses.replace(good, tag=(good.tag[0] ^ 1, good.tag[1]))
    elif forgery == "tag_word1_bit31":
        bad = dataclasses.replace(good, tag=(good.tag[0],
                                             good.tag[1] ^ (1 << 31)))
    else:
        bad = cap                              # a WRITE-only ticket
    checks, to_clients = auth.verifications, router.packets_to_clients
    served, nacks = nodes[2].counts["read_done"], nodes[2].counts["nack"]
    with pytest.raises(IOError, match="denied"):
        client.read(bad, ReplicaCoord(2, 500), data.size)
    # one check for the one shard request, answered by a NACK alone
    assert auth.verifications == checks + 1
    assert router.packets_to_clients == to_clients + 1
    assert nodes[2].counts["nack"] == nacks + 1
    assert nodes[2].counts["read_done"] == served
    assert np.array_equal(client.read(good, ReplicaCoord(2, 500), data.size),
                          data)
    assert auth.verifications == checks + 2
    assert nodes[2].counts["read_done"] == served + 1


def test_cluster_read_checks_each_shard_request():
    from repro.checkpoint.storage import StorageCluster

    k, m = 4, 2
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 20)
    auth = cluster.meta.authority
    blob = np.random.default_rng(4).integers(0, 256, k * 1000, np.uint8)
    before = auth.verifications
    (layout,) = cluster.write_object_bulk([blob], k=k, m=m)
    assert auth.verifications == before + k + m
    before = auth.verifications
    assert cluster.read_objects([layout])[0] == blob.tobytes()
    assert auth.verifications == before + k      # healthy: data shards only
    cluster.fail_node(layout.data_coords[0].node)
    before = auth.verifications
    assert cluster.read_objects([layout])[0] == blob.tobytes()
    assert auth.verifications == before + k + m - 1


def test_req_table_deny_on_full(cluster):
    auth, router, nodes, client, cap = cluster
    small = DFSNode(99, router, auth, req_table_capacity=0)
    client.write(cap, np.zeros(10, np.uint8), [ReplicaCoord(99, 0)])
    assert client.acks()[-1].ctrl == OpType.NACK
    assert small.req_table.denied == 1


def test_cleanup_handler_reclaims_dangling_state(cluster):
    auth, router, nodes, client, cap = cluster
    node = DFSNode(50, router, auth)
    dfs = DFSHeader(OpType.WRITE, 777, 5, cap)
    pkts = packetize_write(dfs, WriteRequestHeader(addr=0, size=5000),
                           np.zeros(5000, np.uint8))
    node.handle_packet(pkts[0])          # header only; client then "dies"
    assert len(node.req_table) == 1
    node.cleanup_stale(alive=set())
    assert len(node.req_table) == 0 and 777 not in node._reqs
