"""Failure injection, degraded reads, and repair — across every plane.

(a) timed plane — degraded-read pipelines compile their survivor fan-out
    against the FailureModel, reconstruct with the NIC decode stage, and
    hold the paper's ratios (degraded <= 2x healthy at RS(3,2) f=1;
    NIC-side reconstruction >= 2x over the host-CPU path);
(b) workload — mixed read/write scenarios share extents on one Env, and
    request/byte conservation holds under crashes and packet loss (no
    silent loss: stuck requests stay in flight, lost packets are counted);
(c) functional plane — packet-plane degraded reads are bit-exact via
    batched RSCode.decode_stripes under any <= m erasures, reconstruction
    is verified against surviving parity, repair rebuilds lost shards onto
    a replacement node, and the audit ledger partitions every written byte.
"""

import numpy as np
import pytest

from repro.policy import FailureModel, PolicySpec, ReadPolicy, RS, SpongeAuth
from repro.policy.timed import ec_read_survivors
from repro.sim import protocols as P
from repro.sim.pspin import PsPINConfig
from repro.sim.workload import KiB, PolicyLoad, Scenario, SizeDist, Workload, run_scenario

MiB = 1 << 20


def _conserves(rep):
    return rep["issued"] == rep["completed"] + rep["in_flight"] + rep["dropped"]


# -- (a) timed plane ---------------------------------------------------------


def test_failure_model_validation():
    with pytest.raises(ValueError, match="probability"):
        FailureModel(loss=((1, 1.5),))
    with pytest.raises(ValueError, match="factor"):
        FailureModel(slow=((1, 0.5),))
    assert FailureModel().is_healthy()
    assert not FailureModel(crashed=(1,)).is_healthy()


def test_read_policy_spec_validation():
    with pytest.raises(ValueError, match="degraded-rs"):
        PolicySpec("spin", SpongeAuth(), op="read",
                   read=ReadPolicy("degraded-rs"))
    with pytest.raises(ValueError, match="replica-failover"):
        PolicySpec("spin", SpongeAuth(), op="read",
                   read=ReadPolicy("replica-failover"))
    with pytest.raises(ValueError, match="unknown read mode"):
        PolicySpec("spin", SpongeAuth(), op="read",
                   read=ReadPolicy("psychic"))
    with pytest.raises(ValueError, match="only applies"):
        PolicySpec("spin", SpongeAuth(), read=ReadPolicy())
    spec = PolicySpec("spin", SpongeAuth(), erasure=RS(3, 2, "spin"),
                      op="read", read=ReadPolicy("degraded-rs"))
    assert spec.storage_node_count == 5
    assert "Read(degraded-rs,spin)" in spec.describe()


def test_ec_read_survivor_selection():
    e = RS(3, 2)
    assert ec_read_survivors(e, set()) == ([1, 2, 3], 0)
    assert ec_read_survivors(e, {2}) == ([1, 3, 4], 1)
    assert ec_read_survivors(e, {1, 3}) == ([2, 4, 5], 2)
    assert ec_read_survivors(e, {4}) == ([1, 2, 3], 0)  # parity loss: direct
    with pytest.raises(ValueError, match="unrecoverable"):
        ec_read_survivors(e, {1, 2, 4})


def test_degraded_read_latency_ordering_and_ratios():
    """The acceptance bar: at RS(3,2) with one failed data node the timed
    degraded read stays <= 2x the healthy spin-read preset, and NIC-side
    reconstruction holds >= 2x over the host-CPU path."""
    pcfg = PsPINConfig(num_hpus=256)  # line-rate decode regime (Fig. 16)
    size = MiB

    def lat(name, failures=None):
        return P.run_degraded_read(name, size, k=3, m=2, failures=failures,
                                   pcfg=pcfg).latency_ns

    healthy = lat("spin-read")
    striped = lat("spin-read-ec")
    deg1 = lat("spin-read-ec", FailureModel(crashed=(1,)))
    deg2 = lat("spin-read-ec", FailureModel(crashed=(1, 2)))
    host1 = lat("cpu-read-ec", FailureModel(crashed=(1,)))
    assert striped <= 1.05 * healthy         # healthy striped read is free
    assert healthy < deg1 < deg2             # reconstruction costs, honestly
    assert deg1 <= 2.0 * healthy             # the paper's degraded bar
    assert host1 >= 2.0 * deg1               # NIC offload holds >= 2x


def test_degraded_read_beyond_m_unrecoverable():
    with pytest.raises(ValueError, match="unrecoverable"):
        P.run_degraded_read("spin-read-ec", 64 * KiB, k=3, m=2,
                            failures=FailureModel(crashed=(1, 2, 3)))


def test_replica_failover_read():
    fo = P.run_degraded_read("spin-read-repl", 64 * KiB, k=3,
                             failures=FailureModel(crashed=(1,)))
    healthy = P.run_degraded_read("spin-read", 64 * KiB)
    assert fo.latency_ns == pytest.approx(healthy.latency_ns, rel=0.01)
    with pytest.raises(ValueError, match="unrecoverable"):
        P.run_degraded_read("spin-read-repl", 4 * KiB, k=2,
                            failures=FailureModel(crashed=(1, 2)))


def test_slow_survivor_stretches_degraded_read():
    """A straggler NIC on the decode path (the client unit, node 0) must
    slow the reconstruction — the FailureModel's slow axis is live."""
    fm = FailureModel(crashed=(1,))
    fast = P.run_degraded_read("spin-read-ec", 256 * KiB, k=3, m=2,
                               failures=fm).latency_ns
    slow = P.run_degraded_read(
        "spin-read-ec", 256 * KiB, k=3, m=2,
        failures=FailureModel(crashed=(1,), slow=((0, 4.0),)),
    ).latency_ns
    assert slow > 1.5 * fast


def test_packet_loss_counted_and_conserved():
    sc = Scenario(protocol="spin-write", size=64 * KiB, num_clients=4,
                  requests_per_client=6, seed=3,
                  failures=FailureModel(loss=((1, 0.05),), seed=11))
    rep = run_scenario(sc)
    assert rep["lost_packets"] > 0
    assert rep["lost_bytes"] > 0
    assert _conserves(rep)
    # requests that lost a packet never ack: they stay visibly in flight
    # (and their closed-loop client stops issuing — no phantom requests)
    assert rep["in_flight"] > 0
    assert rep["completed"] + rep["in_flight"] == rep["issued"] <= 24


def test_crashed_node_strands_writes_without_silent_loss():
    rep = run_scenario(
        Scenario(protocol="spin-write", size=16 * KiB, num_clients=3,
                 requests_per_client=5,
                 failures=FailureModel(crashed=(1,)))
    )
    assert rep["completed"] == 0
    assert rep["in_flight"] == 3      # one stuck request per closed loop
    assert _conserves(rep)


def test_failure_scenarios_deterministic():
    sc = Scenario(protocol="spin-write", size=64 * KiB, num_clients=4,
                  requests_per_client=8, seed=5,
                  failures=FailureModel(loss=((1, 0.1),), seed=2))
    assert run_scenario(sc) == run_scenario(sc)


# -- (b) mixed read/write over shared extents --------------------------------


def _mixed_scenario(**kw):
    base = dict(
        policies=[
            PolicyLoad("spin-write", 1.0, SizeDist("fixed", mean=96 * KiB)),
            PolicyLoad("spin-read-ec", 1.0),
        ],
        size=128 * KiB, num_clients=4, requests_per_client=6,
        k=3, m=2, seed=7, shared_extents=True,
    )
    base.update(kw)
    return Scenario(**base)


def test_shared_extents_reads_consume_written_sizes():
    w = Workload(_mixed_scenario())
    rep = w.run()
    assert _conserves(rep)
    per = rep["per_policy"]
    assert per["spin-read-ec"]["completed"] > 0
    # every completed read drew its size from a completed write's extent
    assert set(w.extents) == {96 * KiB}
    reads = per["spin-read-ec"]
    assert reads["bytes"] == reads["completed"] * 96 * KiB
    assert rep["bytes_read"] == reads["bytes"]
    assert rep["bytes_written"] == per["spin-write"]["bytes"]


def test_shared_extents_early_reads_are_shed_not_lost():
    """A read-only mix never has extents to consume: every read is shed
    and counted as a drop — conservation instead of silent loss."""
    sc = _mixed_scenario(
        policies=[PolicyLoad("spin-read-ec", 1.0)],
        num_clients=2, requests_per_client=4,
    )
    rep = run_scenario(sc)
    assert rep["dropped"] == 8 and rep["completed"] == 0
    assert rep["per_policy"]["spin-read-ec"]["dropped"] == 8
    assert _conserves(rep)


def test_mixed_degraded_reads_under_failure():
    """Writers + degraded readers share the Env while a data node is
    down: reads reconstruct (slower than healthy) and nothing leaks."""
    healthy = run_scenario(_mixed_scenario())
    degraded = run_scenario(
        _mixed_scenario(failures=FailureModel(crashed=(2,))))
    assert _conserves(healthy) and _conserves(degraded)
    h = healthy["per_policy"]["spin-read-ec"]
    d = degraded["per_policy"]["spin-read-ec"]
    assert d["completed"] > 0
    assert d["p99_us"] > h["p99_us"]  # reconstruction is visible in tails


# -- (c) functional plane ----------------------------------------------------


def _cluster_with_object(k=3, m=2, nbytes=50_000, nodes=8, seed=0):
    from repro.checkpoint.storage import StorageCluster

    rng = np.random.default_rng(seed)
    blob = rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
    cluster = StorageCluster(num_nodes=nodes, node_capacity=1 << 22)
    layout = cluster.write_object_bulk([blob], k=k, m=m)[0]
    return cluster, layout, blob


@pytest.mark.parametrize("lost", [(0,), (4,), (0, 1), (0, 3), (3, 4)])
def test_packet_plane_degraded_read_bit_exact(lost):
    """Every <= m erasure pattern: shards fetched via authenticated
    packet reads, reconstructed via batched decode_stripes, bit-exact."""
    cluster, layout, blob = _cluster_with_object()
    coords = list(layout.data_coords) + list(layout.parity_coords)
    for slot in lost:
        cluster.fail_node(coords[slot].node)
    assert cluster.read_object(layout) == blob


def test_degraded_read_verify_catches_corruption():
    cluster, layout, blob = _cluster_with_object()
    # corrupt a surviving parity shard in place, then force reconstruction
    par = layout.parity_coords[1]
    cluster.nodes[par.node].storage.mem[par.addr] ^= 0xFF
    cluster.fail_node(layout.data_coords[0].node)
    with pytest.raises(IOError, match="reconstruction mismatch"):
        cluster.read_object(layout)
    # opting out of verification returns (possibly wrong) bytes silently
    assert cluster.read_object(layout, verify=False) == blob


def test_repair_onto_replacement_node():
    cluster, layout, blob = _cluster_with_object(nodes=8)
    used = {c.node for c in layout.data_coords + layout.parity_coords}
    dead = layout.data_coords[1].node
    replacement = next(n for n in range(8) if n not in used)
    cluster.fail_node(dead)
    stats = cluster.repair_node(dead, replacement=replacement)
    assert stats["shards"] == 1 and stats["unrecoverable"] == 0
    assert layout.data_coords[1].node == replacement
    assert dead in cluster.failed          # dead stays dead; layout moved
    assert cluster.read_object(layout) == blob
    audit = cluster.audit()
    assert audit["readable_bytes"] == audit["bytes_written"]


def test_healthy_ec_read_skips_parity_traffic():
    """A fully healthy EC read fetches only the k data shards — parity
    nodes see no read requests on the fast path."""
    cluster, layout, blob = _cluster_with_object()
    assert cluster.read_object(layout) == blob
    for coord in layout.parity_coords:
        assert cluster.nodes[coord.node].counts["read_done"] == 0


def test_background_repair_invalid_replacement_raises_on_caller():
    """Argument validation happens before the repair thread spawns, and a
    repair that died never reads as success via stale stats."""
    cluster, layout, _ = _cluster_with_object(nodes=8)
    dead = layout.data_coords[0].node
    other = layout.data_coords[1].node
    cluster.fail_node(dead)
    cluster.fail_node(other)
    with pytest.raises(ValueError, match="is failed"):
        cluster.repair_node(dead, replacement=other, background=True)


def test_background_repair_in_place():
    cluster, layout, blob = _cluster_with_object()
    dead = layout.parity_coords[0].node
    cluster.fail_node(dead)
    assert cluster.repair_node(dead, background=True) is None
    stats = cluster.repair_wait()
    assert stats["shards"] >= 1
    assert dead not in cluster.failed
    assert cluster.read_object(layout) == blob


def test_in_place_repair_beyond_tolerance_pins_object_lost():
    """Re-provisioning a node whose shards cannot be reconstructed must
    not resurrect zeroed shards as readable: the object is pinned lost,
    reads raise, and the audit ledger keeps the bytes in lost_bytes."""
    cluster, layout, blob = _cluster_with_object(k=3, m=2)
    dead = [layout.data_coords[0], layout.parity_coords[0],
            layout.parity_coords[1]]
    for coord in dead:
        cluster.fail_node(coord.node)       # 3 > m: unrecoverable
    stats = cluster.repair_node(dead[0].node)   # in-place re-provision
    assert stats["unrecoverable"] == 1 and stats["shards"] == 0
    assert layout.lost
    with pytest.raises(IOError, match="lost"):
        cluster.read_object(layout)
    audit = cluster.audit()
    assert audit["lost_bytes"] == len(blob)
    assert audit["readable_bytes"] == 0


def test_deep_shed_read_run_does_not_recurse():
    """A long closed-loop run of shed reads iterates through the event
    queue instead of recursing one stack frame per request."""
    sc = _mixed_scenario(
        policies=[PolicyLoad("spin-read-ec", 1.0)],
        num_clients=1, requests_per_client=1200,
    )
    rep = run_scenario(sc)
    assert rep["dropped"] == 1200 and _conserves(rep)


def test_background_repair_serializes_with_foreground_writes():
    """The repair thread and foreground packet-plane ops share the I/O
    lock: a write issued while a repair is in flight must not lose acks
    to interleaved router drains."""
    cluster, layout, blob = _cluster_with_object(nodes=8)
    dead = layout.parity_coords[0].node
    cluster.fail_node(dead)
    cluster.repair_node(dead, background=True)
    lay2 = cluster.write_object_bulk([blob], k=3, m=2)[0]
    assert cluster.repair_wait()["shards"] >= 1
    assert cluster.read_object(layout) == blob
    assert cluster.read_object(lay2) == blob


def test_audit_partitions_every_written_byte():
    cluster, layout, blob = _cluster_with_object(k=3, m=2)
    a = cluster.audit()
    assert a["readable_bytes"] == a["bytes_written"] == len(blob)
    cluster.fail_node(layout.data_coords[0].node)
    a = cluster.audit()
    assert a["reconstructable_bytes"] == len(blob) and a["lost_bytes"] == 0
    cluster.fail_node(layout.data_coords[1].node)
    cluster.fail_node(layout.parity_coords[0].node)
    a = cluster.audit()
    assert a["lost_bytes"] == len(blob)    # beyond m: accounted, not silent
    with pytest.raises((ValueError, IOError)):
        cluster.read_object(layout)


def test_placement_avoids_failed_nodes_and_write_retries():
    """New objects never land on crashed nodes, and a write whose layout
    was placed *before* the crash re-places on live nodes and retries
    (the mid-save crash race of the resilient-training loop)."""
    from repro.checkpoint.storage import StorageCluster

    cluster = StorageCluster(num_nodes=9, node_capacity=1 << 22)
    blob = np.arange(40_000, dtype=np.uint8) % 251
    cluster.fail_node(2)
    lay = cluster.write_object_bulk([blob.tobytes()], k=3, m=2)[0]
    nodes = {c.node for c in lay.data_coords + lay.parity_coords}
    assert 2 not in nodes
    assert cluster.read_object(lay) == blob.tobytes()
    # placement done, THEN the node dies, THEN the shards are written:
    from repro.core.packets import Resiliency

    stale = cluster.meta.create_object(
        int(blob.size), Resiliency.ERASURE_CODING, 3, 2)
    cluster.fail_node(stale.data_coords[0].node)
    orig = cluster.meta.create_object
    calls = {"n": 0}

    def place(*a, **kw):
        calls["n"] += 1
        return stale if calls["n"] == 1 else orig(*a, **kw)

    cluster.meta.create_object = place
    try:
        lay2 = cluster.write_object(blob.tobytes(), k=3, m=2)
    finally:
        cluster.meta.create_object = orig
    assert calls["n"] == 2                    # the write re-placed and retried
    assert stale.object_id not in cluster.meta._objects  # dead layout dropped
    nodes2 = {c.node for c in lay2.data_coords + lay2.parity_coords}
    assert not (nodes2 & cluster.failed)
    assert cluster.read_object(lay2) == blob.tobytes()


def test_checkpoint_restore_batches_degraded_decode():
    """CheckpointManager.restore routes every same-pattern stripe of a
    leaf through one batched decode_stripes call and survives m losses."""
    from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
    from repro.checkpoint.storage import StorageCluster

    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 23)
    mgr = CheckpointManager(
        cluster, CheckpointPolicy(k=4, m=2, stripe_bytes=1 << 14))
    rng = np.random.default_rng(3)
    tree = {"w": rng.standard_normal((96, 128)).astype(np.float32)}
    mgr.save(1, tree, blocking=True)
    cluster.fail_node(0)
    cluster.fail_node(5)
    got = mgr.restore(1, treedef=tree)
    assert np.array_equal(got["w"], tree["w"])


# -- functional-plane reads under live packet loss (bounded retry) -----------


def _lossy_cluster(objects=6, loss=((0, 0.6), (1, 0.6), (2, 0.6)), seed=1):
    from repro.checkpoint.storage import StorageCluster

    rng = np.random.default_rng(7)
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 22)
    blobs = [rng.integers(0, 256, 64 * KiB, dtype=np.uint8).tobytes()
             for _ in range(objects)]
    layouts = cluster.write_object_bulk(blobs, k=3, m=2)
    cluster.set_failures(FailureModel(loss=loss, seed=seed))
    return cluster, layouts, blobs


def test_lossy_reads_retry_and_recover_bit_exact():
    """A lossy link drops shard reads; the bounded retry budget recovers
    them and the retries are counted in the audit ledger."""
    cluster, layouts, blobs = _lossy_cluster()
    assert cluster.read_objects(layouts) == blobs
    audit = cluster.audit()
    assert audit["read_retries"] > 0
    assert audit["read_retries"] == cluster.read_retries
    # no shard exhausted its budget at this loss rate/seed
    assert audit["read_timeouts"] == 0


def test_total_loss_times_out_into_degraded_reconstruction():
    """100% loss towards one node exhausts the retry budget (the
    functional-plane timeout); the read falls into the degraded decode
    path and still returns bit-exact data."""
    cluster, layouts, blobs = _lossy_cluster(loss=((0, 1.0),))
    assert cluster.read_objects(layouts) == blobs
    audit = cluster.audit()
    assert audit["read_timeouts"] > 0
    # every timed-out shard first burned its whole retry budget
    assert cluster.read_retries >= (cluster.max_read_retries
                                    * cluster.read_timeouts)


def test_lossy_reads_deterministic():
    """The loss draw is seeded: identical clusters produce identical
    retry/timeout ledgers."""
    a, la, _ = _lossy_cluster()
    b, lb, _ = _lossy_cluster()
    a.read_objects(la)
    b.read_objects(lb)
    assert (a.read_retries, a.read_timeouts) == (b.read_retries,
                                                 b.read_timeouts)


def test_set_failures_crashes_and_losses():
    """FailureModel attach: crashed nodes blackhole (degraded reads
    reconstruct), lossy nodes retry — both at once, all accounted."""
    cluster, layouts, blobs = _lossy_cluster(loss=((0, 0.5),))
    cluster.set_failures(FailureModel(crashed=(1,), loss=((0, 0.5),), seed=1))
    assert cluster.read_objects(layouts) == blobs
    audit = cluster.audit()
    assert 1 in cluster.failed
    assert audit["readable_bytes"] + audit["reconstructable_bytes"] \
        + audit["lost_bytes"] == audit["bytes_written"]


def test_paced_repair_throttles_rebuild():
    """RepairPacer bounds the rebuild byte rate: the same governor the
    workload engine paces its background loads with, on the wall clock
    (injected here so the test is instant and deterministic)."""
    from repro.control import RepairPacer

    cluster, layouts, blobs = _lossy_cluster(loss=())
    t = {"now": 0.0}
    slept = []

    def sleep(s):
        slept.append(s)
        t["now"] += s

    pacer = RepairPacer(rate_MBps=0.5, burst_bytes=32 * KiB,
                        clock=lambda: t["now"], sleep=sleep)
    dead = layouts[0].data_coords[0].node
    cluster.fail_node(dead)
    stats = cluster.repair_node(dead, pacer=pacer)
    assert stats["paced_wait_s"] > 0 and slept
    assert stats["paced_wait_s"] == pytest.approx(sum(slept))
    # the configured rate held: total wall time >= bytes / rate (minus
    # the initial burst allowance)
    assert t["now"] >= (stats["bytes"] - 32 * KiB) / 0.5e6
    for lay, blob in zip(layouts, blobs):
        assert cluster.read_object(lay) == blob


def test_paced_repair_interleaves_with_foreground_reads():
    """The pacer's wait is served *outside* the cluster I/O lock, and
    the node stays failed until write-back completes: a foreground read
    issued mid-rebuild acquires the lock, treats the half-rebuilt node
    as missing, and reconstructs correct bytes (never zeroed shards)."""
    from repro.control import RepairPacer

    cluster, layouts, blobs = _lossy_cluster(loss=())
    dead = layouts[0].data_coords[0].node
    cluster.fail_node(dead)
    mid_reads = []

    def sleep(_s):
        # runs between shard write-backs, with the lock released
        assert dead in cluster.failed
        mid_reads.append(cluster.read_objects(layouts) == blobs)

    t = {"now": 0.0}
    pacer = RepairPacer(rate_MBps=0.5, burst_bytes=16 * KiB,
                        clock=lambda: t["now"], sleep=sleep)
    cluster.repair_node(dead, pacer=pacer)
    assert mid_reads and all(mid_reads)
    assert dead not in cluster.failed
    assert cluster.read_objects(layouts) == blobs
