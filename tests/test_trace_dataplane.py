"""Wall-clock tracing and counters of the served data plane.

``repro.trace.wall`` spans cost nothing while no tracer is installed;
installed, they nest by thread (a checkpoint save's writer thread under
the save's root), stay bounded, and render through the same Perfetto
exporter as the simulator's spans.  The always-on counters count what
the operations must send.
"""

import sys
import threading
import time

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.checkpoint.storage import StorageCluster
from repro.core.packets import DFSHeader, WriteRequestHeader, num_packets
from repro.kernels import ops
from repro.trace import (Span, Tracer, dataplane_registry,
                         to_chrome_trace, wall)

pytestmark = pytest.mark.trace


@pytest.fixture
def tracer():
    tr = Tracer.wall()
    wall.install(tr)
    try:
        yield tr
    finally:
        wall.uninstall()


def _by_name(tr, name):
    return [s for s in tr.spans if s.name == name]


def test_off_span_is_the_shared_null_and_allocates_nothing():
    assert wall.installed() is None
    idle = Tracer.wall()                # made but never installed
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 20)
    cluster.read_objects(cluster.write_object_bulk([b"x" * 3000], k=3, m=2))
    assert len(idle) == 0 and idle.dropped == 0
    assert wall.span("dfs.write", "packet") is wall.NULL
    assert wall.begin("ckpt.save", "entry") is None
    with wall.span("dfs.write", "packet") as sp:
        assert sp is None
    before = sys.getallocatedblocks()
    for _ in range(10_000):
        with wall.span("dfs.write", "packet"):
            pass
    # one block per call would show as 10,000
    assert sys.getallocatedblocks() - before < 1000


def test_install_needs_a_wall_tracer_and_one_at_a_time():
    with pytest.raises(ValueError):
        wall.install(Tracer(sample_every=1))
    with pytest.raises(ValueError):
        Tracer(sample_every=4, clock="wall")
    tr = Tracer.wall()
    wall.install(tr)
    try:
        with pytest.raises(RuntimeError):
            wall.install(Tracer.wall())
    finally:
        assert wall.uninstall() is tr
    assert wall.installed() is None


def test_nested_spans_parent_and_rid_on_two_threads(tracer):
    def work(tag):
        with wall.span(f"outer.{tag}", "entry") as outer:
            with wall.span(f"inner.{tag}", "coding") as inner:
                assert inner.parent is outer

    t = threading.Thread(target=work, args=("b",), name="other")
    work("a")
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    (oa,), (ia,) = _by_name(tracer, "outer.a"), _by_name(tracer, "inner.a")
    (ob,), (ib,) = _by_name(tracer, "outer.b"), _by_name(tracer, "inner.b")
    assert oa.parent is None and ob.parent is None
    assert ia.parent is oa and ib.parent is ob
    assert ia.rid == oa.rid != ob.rid == ib.rid
    assert (oa.resource, ob.resource) == (threading.current_thread().name,
                                          "other")
    assert ib.cat == "coding"
    for s in tracer.spans:
        assert s.t0 <= s.t1
    assert oa.t0 <= ia.t0 <= ia.t1 <= oa.t1


def test_save_worker_spans_nest_under_the_save(tracer):
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 22)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=2,
                                                      stripe_bytes=1 << 14))
    tree = {"w": np.arange(5000, dtype=np.float32), "b": np.ones(3)}
    mgr.save(1, tree, blocking=True)
    (root,) = _by_name(tracer, "ckpt.save")
    (snap,) = _by_name(tracer, "ckpt.snapshot")
    (wait,) = _by_name(tracer, "ckpt.wait")
    leaves = _by_name(tracer, "ckpt.leaf")
    assert len(leaves) == 2 and len(_by_name(tracer, "ckpt.mac")) == 2
    main = threading.current_thread().name
    assert snap.parent is root and snap.resource == main
    assert wait.parent is root and snap.t1 <= wait.t0
    for leaf in leaves:
        assert leaf.parent is root and leaf.rid == root.rid
        assert leaf.resource != main
    for s in tracer.spans:
        if s is not root:
            assert s.rid == root.rid
    writes = _by_name(tracer, "cluster.write")
    assert {w.parent for w in writes} == set(leaves)
    for name, parent in [("rs.encode", "cluster.write"),
                         ("dfs.write", "cluster.write"),
                         ("dfs.frame", "dfs.write"),
                         ("auth.verify", "dfs.write"),
                         ("ckpt.mac", "ckpt.leaf")]:
        spans = _by_name(tracer, name)
        assert spans and {s.parent.name for s in spans} == {parent}, name
    # save_seconds is the ckpt.save span's own clock, less its wait
    assert mgr.save_seconds == [
        (root.t1 - root.t0 - (wait.t1 - wait.t0)) / 1e9]
    assert root.t0 <= snap.t0 and max(s.t1 for s in leaves) <= root.t1


def test_async_save_leaves_the_wait_for_the_last_one_out(tracer,
                                                         monkeypatch):
    """Back-to-back async saves: the second waits for the first's slow
    writer under ``ckpt.wait``, which its ``save_seconds`` leaves out."""
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 22)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=2))
    write = mgr._write_leaves

    def slow_first(step, snap, parent):
        if step == 1:
            time.sleep(0.5)
        return write(step, snap, parent)

    monkeypatch.setattr(mgr, "_write_leaves", slow_first)
    mgr.save(1, {"w": np.ones(10)})
    mgr.save(2, {"w": np.zeros(10)})
    mgr.wait()
    assert mgr.latest_step() == 2
    roots = sorted(_by_name(tracer, "ckpt.save"), key=lambda s: s.t0)
    waits = {w.parent: w for w in _by_name(tracer, "ckpt.wait")}
    assert set(waits) == set(roots)
    second, waited = roots[1], waits[roots[1]]
    assert waited.t1 - waited.t0 > 0.25e9      # the first writer's sleep
    assert waited.t1 >= roots[0].t1
    assert mgr.save_seconds[1] == (second.t1 - second.t0
                                   - (waited.t1 - waited.t0)) / 1e9
    assert sum(mgr.save_seconds) < (roots[1].t1 - roots[0].t0) / 1e9


def test_failed_snapshot_leaves_a_counter_and_a_failed_span(tracer):
    class Unreadable:
        def __array__(self, *args, **kwargs):
            raise RuntimeError("device lost")

    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 22)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=2))
    with pytest.raises(RuntimeError, match="device lost"):
        mgr.save(1, {"w": Unreadable()}, blocking=True)
    assert mgr.failed_saves == 1 and mgr.save_seconds == []
    assert mgr.latest_step() is None
    (root,) = _by_name(tracer, "ckpt.save")
    (snap,) = _by_name(tracer, "ckpt.snapshot")
    assert root.args == {"failed": True} and snap.args == {"failed": True}
    assert snap.parent is root and not _by_name(tracer, "ckpt.leaf")


def test_failed_save_leaves_a_counter_and_a_failed_span(tracer, monkeypatch):
    cluster = StorageCluster(num_nodes=8, node_capacity=1 << 22)
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=4, m=2))

    def broken(*args, **kwargs):
        raise OSError("node on fire")

    monkeypatch.setattr(cluster, "write_object_bulk", broken)
    monkeypatch.setattr(threading, "excepthook", lambda args: None)
    mgr.save(1, {"w": np.ones(10)}, blocking=True)
    assert mgr.failed_saves == 1 and mgr.save_seconds == []
    assert mgr.latest_step() is None
    (root,) = _by_name(tracer, "ckpt.save")
    (leaf,) = _by_name(tracer, "ckpt.leaf")
    assert root.args == {"failed": True} and leaf.args == {"failed": True}
    reg = dataplane_registry(cluster, mgr).snapshot()
    assert reg["ckpt.failed_saves"] == 1 and reg["ckpt.saves"] == 0


def test_bound_counts_dropped(tracer):
    tracer.max_spans = 5
    for _ in range(8):
        with wall.span("x", "entry"):
            pass
    assert len(tracer) == 5 and tracer.dropped == 3


def test_record_and_keep_share_the_bound():
    tr = Tracer(sample_every=1, max_spans=2)
    assert tr.record("a", "wire", 0, 1).name == "a"
    assert tr.keep(Span("b", "wire", 1, 2)) is True
    assert tr.record("c", "wire", 2, 3) is None
    assert tr.keep(Span("d", "wire", 3, 4)) is False
    assert [s.name for s in tr.spans] == ["a", "b"] and tr.dropped == 2


def test_chrome_trace_renders_a_dataplane_tracer(tracer):
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 20)
    (layout,) = cluster.write_object_bulk([bytes(range(256)) * 40], k=3, m=2)
    cluster.read_objects([layout])
    doc = to_chrome_trace(tracer)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(events) == len(tracer.spans)
    names = {e["name"] for e in events}
    assert {"cluster.write", "cluster.read", "dfs.write", "dfs.read",
            "dfs.assemble", "rs.encode", "auth.verify"} <= names
    threads = [e for e in doc["traceEvents"] if e.get("name") == "thread_name"]
    assert {t["args"]["name"] for t in threads} == {
        threading.current_thread().name}
    child = next(e for e in events if e["name"] == "dfs.write")
    assert child["cat"] == "packet" and child["args"]["parent"] == (
        "cluster.write")


def test_counters_count_what_a_write_must_send():
    k, m, length = 6, 3, 20_000
    cluster = StorageCluster(num_nodes=12, node_capacity=1 << 22)
    reg = dataplane_registry(cluster)
    blob = np.random.default_rng(0).integers(0, 256, k * length, np.uint8)
    a = reg.snapshot()
    (layout,) = cluster.write_object_bulk([blob], k=k, m=m, backend="jax")
    b = reg.snapshot()
    d = reg.diff(a, b)
    assert layout.chunk_len == length
    # the first packet carries the headers, the rest 2048 - 28 B each
    head = 28 + DFSHeader.packed_size() + WriteRequestHeader(0, length
                                                             ).packed_size()
    per_shard = 1 + -(-(length - (2048 - head)) // (2048 - 28))
    assert per_shard == num_packets(length, head - 28 - DFSHeader.packed_size())
    assert d["packets.to_nodes"] == (k + m) * per_shard
    assert d["packets.to_clients"] == k + m          # one ack per shard
    assert d["auth.verifications"] == k + m
    assert d["node.write_done"] == k + m
    assert (d["codec.dispatches"], d["codec.stripes"]) == (1, 1)
    # 20,000 B is off the tile: the program pads it
    assert d["codec.padded"] == 1
    assert d["codec.h2d_bytes"] == k * length
    assert d["codec.d2h_bytes"] == m * length
    cluster.fail_node(layout.data_coords[0].node)
    c = reg.snapshot()
    assert cluster.read_objects([layout], backend="jax")[0] == blob.tobytes()
    d = reg.diff(c, reg.snapshot())
    read_pkts = -(-length // (2048 - 28))
    # k - 1 data + m parity shards answer; the failed node gets no request
    assert d["packets.to_nodes"] == k + m - 1
    assert d["packets.dropped"] == 0
    assert d["packets.to_clients"] == (k + m - 1) * read_pkts
    assert d["node.read_done"] == k + m - 1
    # decode, then the verify re-encode: two S = 1 dispatches
    assert (d["codec.dispatches"], d["codec.stripes"]) == (2, 2)
    assert d["codec.padded"] == 2


def test_codec_counts_are_process_wide():
    before = dict(ops.CODEC_COUNTS)
    data = np.zeros((3, 2, 64), np.uint8)
    ops.to_host(ops.gf_matmul_bytes_batched(np.ones((1, 2), np.uint8), data))
    # S = 3 goes as power-of-two pieces: 2 stripes, then 1
    assert ops.CODEC_COUNTS["dispatches"] == before["dispatches"] + 2
    assert ops.CODEC_COUNTS["stripes"] == before["stripes"] + 3
    assert ops.CODEC_COUNTS["padded"] == before["padded"] + 2
    assert ops.CODEC_COUNTS["h2d_bytes"] == before["h2d_bytes"] + data.nbytes
    assert ops.CODEC_COUNTS["d2h_bytes"] == before["d2h_bytes"] + 3 * 64


def test_codec_spans_split_the_dispatch(tracer):
    data = np.zeros((2, 2, 64), np.uint8)
    ops.to_host(ops.gf_matmul_bytes_batched(np.ones((1, 2), np.uint8), data))
    assert [s.name for s in tracer.spans] == [
        "codec.h2d", "codec.launch", "codec.d2h"]
    assert {s.cat for s in tracer.spans} == {"coding"}


def test_codec_program_carries_pack_and_unpack_scopes():
    text = ops.gf_matmul_program(3, 6, 1, 4096).as_text(debug_info=True)
    assert "_encode_planes_batched)/rs_pack/" in text
    assert "_encode_planes_batched)/rs_unpack/" in text
    assert "rs_gf_matmul" in text


def test_codec_program_lowers_the_unpadded_piece():
    """An off-tile chunk lowers as the dispatch sends it, (S, k, L) in
    and (S, n, L) out, with the pad and the trim inside the program."""
    lowered = ops.gf_matmul_program(3, 6, 2, 3904)
    assert [a.shape for a in lowered.in_avals[0]] == [(3, 6, 8, 8),
                                                      (2, 6, 3904)]
    assert lowered.out_info.shape == (2, 3, 3904)
    text = lowered.as_text(debug_info=True)
    assert "_encode_planes_batched)/rs_pack/" in text
    assert "rs_gf_matmul" in text


def test_node_counts_replace_the_event_list():
    cluster = StorageCluster(num_nodes=6, node_capacity=1 << 20)
    (layout,) = cluster.write_object_bulk([b"x" * 3000], k=3, m=2)
    for node in cluster.nodes:
        assert set(node.counts) == {
            "deny_full", "ec_cpu_fallback", "parity_done", "write_done",
            "nack", "read_done", "cleanup"}
        assert not hasattr(node, "events")
    held = [c.node for c in list(layout.data_coords)
            + list(layout.parity_coords)]
    assert sum(cluster.nodes[n].counts["write_done"] for n in held) == 5


def test_namenode_spans_and_counters_of_one_create_write_close(tracer):
    """One 3,901-byte file in an RS-6-3-1024k directory: the NameNode's
    spans in the order of its RPCs, the write under ``nn.add_block``, and
    its counters moved by one open, one commit, one block group, 4
    internal blocks written and 5 left empty."""
    from repro.namenode import ECPolicy, NameNode
    from repro.trace import namenode_registry

    cluster = StorageCluster(num_nodes=12, node_capacity=1 << 20)
    nn = NameNode(cluster)
    nn.mkdir("/mdtest-hard")
    nn.set_ec_policy("/mdtest-hard", ECPolicy(6, 3, 1 << 20))
    reg = namenode_registry(nn)
    before = reg.snapshot()
    start = len(tracer.spans)
    nn.create("/mdtest-hard/file.mdtest.0.0")
    nn.add_block("/mdtest-hard/file.mdtest.0.0", bytes(3901))
    spans = tracer.spans[start:]
    nn_spans = [s for s in spans if s.name.startswith("nn.")]
    assert [s.name for s in nn_spans] == ["nn.create", "nn.add_block",
                                         "nn.commit"]
    assert [s.t0 for s in nn_spans] == sorted(s.t0 for s in nn_spans)
    assert all(s.cat == "metadata" for s in nn_spans)
    (write,) = _by_name(tracer, "cluster.write")
    assert write.parent is nn_spans[1]
    moved = {n: v for n, v in reg.diff(before, reg.snapshot()).items()
             if v and n.startswith("nn.")}
    assert moved == {"nn.opens": 1, "nn.commits": 1, "nn.block_groups": 1,
                     "nn.internal_blocks": 4, "nn.empty_internal_blocks": 5}
    # the packet plane carried the 4 internal blocks and nothing else
    assert reg.diff(before, reg.snapshot())["node.write_done"] == 4
