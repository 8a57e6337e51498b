"""The program's own records as the benchmark reads them: the data
plane's counters as differences over a window that moves to a fresh
cluster part way, the ``codec_kernel_share`` reader on hand-built device
events and on the committed TPU traces (``data/encode_s1.xplane.pb``:
three encodes, no program spans; ``program_trace/program_s1.xplane.pb``:
writes and a degraded read with the program's tracer installed, kept
apart because the reduce tests read every trace under ``data/``), and
the program's spans in that trace's host plane, on the device's clock."""

import os

import pytest

from chipbench import cells, harness, reduce
from chipbench.harness import Run, reader
from repro.core.packets import WriteRequestHeader, num_packets
from repro.kernels import ops
from repro.trace import Tracer, dataplane_registry, wall

HERE = os.path.dirname(__file__)
TPU = "/device:TPU:0"
KERNEL_OP = 'custom_call_target="tpu_custom_call"'
FIXTURES = ["data/encode_s1.xplane.pb", "program_trace/program_s1.xplane.pb"]
#: the program's spans on the path the second fixture records
PATH_SPANS = {"cluster.write", "cluster.read", "dfs.write", "dfs.frame",
              "dfs.read", "dfs.assemble", "auth.verify", "rs.encode",
              "rs.decode", "codec.h2d", "codec.launch", "codec.d2h"}


def _planes(path):
    import jax

    return list(jax.profiler.ProfileData.from_file(
        os.path.join(HERE, path)).planes)


def _host_notes(planes, names):
    """``(name, start, end)`` of the host plane's annotations in
    ``names``, by start."""
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for p in planes if not p.name.startswith("/device:")
                   for line in p.lines for e in line.events
                   if e.name in names), key=lambda n: n[1])


def _fixture_run(planes):
    """The fixture as a run's trace: the window is its annotation."""
    ((_, s, e),) = _host_notes(planes, {reduce.WINDOW_ANNOTATION})
    trace = reduce.DeviceTrace.from_planes(planes, int(s))
    return Run([], [(int(s), int(e))], 1.0, [], trace, "TPU v5 lite")


def _counted_window(cell, seconds, tracer):
    """``measure_window`` with the data plane's counters summed over its
    pieces, each read on its own cluster's registry, and the tracer
    installed; a ``rotate()`` (which retires and checks a full cluster)
    is left out of both."""
    total, opened = {}, []

    def start():
        reg = dataplane_registry(cell.cluster)
        opened.append((reg, reg.snapshot()))
        wall.install(tracer)

    def stop():
        wall.uninstall()
        reg, before = opened.pop()
        for name, d in reg.diff(before, reg.snapshot()).items():
            total[name] = total.get(name, 0) + d

    rotate = cell.rotate

    def paused_rotate():
        stop()
        rotate()
        start()

    cell.rotate = paused_rotate
    start()
    try:
        ops_, window = harness.measure_window(cell, seconds, None)
    finally:
        stop()
        del cell.rotate
    return ops_, window, total


def test_window_counters_stay_exact_across_a_mid_window_rotate(monkeypatch):
    """Tiny 6+3 writes on the kernels (interpreted), a cluster too small
    for the window: the counters count what the operations must send,
    and the rotation's own check (which reads every object back) is left
    out of the counters and of the spans."""
    monkeypatch.setattr(ops, "dataplane_backend",
                        lambda backend=None: backend or "jax")
    cell_name = "hdfs-rs6-3-1m.stream-write"
    entry, config, traffic = harness.cell_files(harness.load_bench(),
                                                cell_name)
    # 24 KiB objects, four to a cluster
    traffic.update(object_bytes=24 << 10, pool_objects=4, readback_objects=3,
                   cluster_bytes=4 * 9 * (4 << 10))
    cell = cells.make(config, traffic, 4000000007)
    cell.setup()
    tracer = Tracer.wall()
    ops_, window, c = _counted_window(cell, 0.5, tracer)
    assert len(window) >= 2, "the window never moved to a fresh cluster"
    writes = len(ops_) * traffic["objects_per_op"]
    shard = cell.chunk(traffic["object_bytes"])
    shards = writes * (cell.k + cell.m)
    assert c["packets.to_nodes"] == shards * num_packets(
        shard, WriteRequestHeader(0, shard).packed_size())
    assert c["packets.to_clients"] == shards            # one ack a shard
    assert c["auth.verifications"] == c["node.write_done"] == shards
    assert c["node.read_done"] == 0                     # no check read
    assert c["codec.dispatches"] == len(ops_)
    assert c["codec.stripes"] == writes
    assert c["codec.h2d_bytes"] == writes * cell.k * shard
    assert c["codec.d2h_bytes"] == writes * cell.m * shard
    # spans only inside the window's pieces, none in the rotations
    assert tracer.spans
    for s in tracer.spans:
        assert any(a <= s.t0 <= s.t1 <= b for a, b in window), s.name
    assert sum(s.name == "cluster.write" for s in tracer.spans) == len(ops_)


@pytest.mark.parametrize("name", ["codec_kernel_share.write",
                                  "codec_kernel_share.read",
                                  "codec_kernel_share.repair"])
def test_kernel_share_reader_by_hand(name):
    """The codec program 30-50 holds the kernel 35-38 and the pack and
    unpack around it; another program's custom call does not count."""
    trace = reduce.DeviceTrace(
        {TPU: [("%pack = fusion()", 30, 35),
               (f"%k = custom-call(), {KERNEL_OP}", 35, 38),
               ("%unpack = fusion()", 38, 50),
               (f"%other = custom-call(), {KERNEL_OP}", 60, 70)]},
        {TPU: [("jit__encode_planes_batched(1)", 30, 50),
               ("jit_other(2)", 60, 70)]})
    read = reader(name)
    run = Run([], [(0, 100)], 1.0, [], trace, "TPU v5 lite")
    assert read(run) == pytest.approx(15)          # 3 of the program's 20
    # only what falls in the window: 36-38 of the program's 36-50
    run.window = [(36, 100)]
    assert read(run) == pytest.approx(100 * 2 / 14)
    run.window = [(55, 100)]                        # no codec program
    assert read(run) is None
    run.trace = None
    assert read(run) is None


def _kernel_share_by_hand(planes):
    kernel = total = 0
    for plane in planes:
        if plane.name != TPU:
            continue
        for line in plane.lines:
            for e in line.events:
                if line.name == reduce.MODULES_LINE and (
                        reduce.CODEC_PROGRAM in e.name):
                    total += e.duration_ns
                elif line.name == reduce.OPS_LINE and KERNEL_OP in e.name:
                    kernel += e.duration_ns
    return 100 * kernel / total


@pytest.mark.parametrize("path", FIXTURES)
def test_fixture_kernel_share_by_hand(path):
    planes = _planes(path)
    share = reader("codec_kernel_share.write")(_fixture_run(planes))
    assert share == pytest.approx(_kernel_share_by_hand(planes))
    # S = 1 at 6 MiB: the pack and unpack take nearly all the program
    assert 0.2 < share < 1


@pytest.fixture(scope="module")
def program_planes():
    return _planes("program_trace/program_s1.xplane.pb")


def test_program_fixture_holds_every_span_of_the_path(program_planes):
    ((_, ws, we),) = _host_notes(program_planes, {reduce.WINDOW_ANNOTATION})
    notes = _host_notes(program_planes, PATH_SPANS)
    assert {n for n, _, _ in notes} == PATH_SPANS
    for _, s, e in notes:
        assert ws <= s <= e <= we


def test_program_fixture_counts_its_dispatches(program_planes):
    trace = _fixture_run(program_planes).trace
    codec = sum(reduce.CODEC_PROGRAM in n for n, _, _ in trace.modules[TPU])
    notes = _host_notes(program_planes, {"codec.launch"})
    # two writes and the read's verify encode, and its decode
    assert codec == len(notes) == 4


def test_program_fixture_spans_and_device_share_one_clock(program_planes):
    """Each codec program runs on the device after its dispatch's
    ``codec.launch`` opened and before its ``codec.d2h`` (the wait and
    the copy) closed, inside its ``rs.*`` span: the program's
    annotations and the device's events, read with no shift."""
    progs = sorted((s, s + e.duration_ns) for p in program_planes
                   if p.name == TPU for line in p.lines
                   if line.name == reduce.MODULES_LINE
                   for e in line.events if reduce.CODEC_PROGRAM in e.name
                   for s in [e.start_ns])
    notes = _host_notes(program_planes,
                        {"codec.launch", "codec.d2h", "rs.encode",
                         "rs.decode"})
    by = {n: [(s, e) for m, s, e in notes if m == n]
          for n in ("codec.launch", "codec.d2h")}
    coding = [(s, e) for n, s, e in notes if n.startswith("rs.")]
    assert len(progs) == len(coding) == 4
    for (ps, pe), (ls, _), (_, de), (cs, ce) in zip(
            progs, by["codec.launch"], by["codec.d2h"], coding):
        assert cs <= ls < ps < pe <= de <= ce
