"""The program's own records as the benchmark reads them: the counters
and spans a traced run's harness hands the readers, exact over a window
that moves to a fresh cluster part way; the readers of the program's
spans (``packet_us``, ``codec_host_share``, ``ckpt_snapshot_share``) and
the charger of device idle time, by hand, on the committed TPU trace
``program_trace/program_s1.xplane.pb`` (writes and a degraded read with
the program's tracer installed, kept apart because the reduce tests read
every trace under ``data/``), where the program's spans lie in the host
plane on the device's clock; and the ``codec_kernel_share`` reader on
hand-built device events and on both committed traces
(``data/encode_s1.xplane.pb``: three encodes, no program spans)."""

import os

import pytest

from chipbench import harness, reduce
from chipbench.harness import Run, reader
from repro.core.packets import WriteRequestHeader, num_packets
from repro.kernels import ops
from repro.trace import Span, wall

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


def test_window_counters_stay_exact_across_a_mid_window_rotate(
        run_cell, monkeypatch):
    """A traced run of tiny 6+3 writes on the kernels (interpreted), a
    cluster too small for the window: the harness's counters count what
    the operations must send, and the rotation's own check (which reads
    every object back) is left out of the counters and of the program's
    spans."""
    monkeypatch.setattr(ops, "dataplane_backend",
                        lambda backend=None: backend or "jax")
    runs = []

    class Kept(Run):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Kept)
    cell_name = "hdfs-rs6-3-1m.stream-write"
    _, _, traffic = harness.cell_files(harness.load_bench(), cell_name)
    # 24 KiB objects, four to a cluster
    sizes = dict(object_bytes=24 << 10, pool_objects=4, readback_objects=3,
                 cluster_bytes=4 * 9 * (4 << 10))
    result, out, _ = run_cell(cell_name, 4000000007, trace=1, seconds=0.5,
                              overrides={"traffic": sizes})
    assert result["correct"]
    (run,) = runs
    window, c = run.window, run.counters
    assert len(window) >= 2, "the window never moved to a fresh cluster"
    writes = len(run.ops) * traffic["objects_per_op"]
    k, m = 6, 3
    shard = 4 << 10                     # 24 KiB over 6 data cells
    shards = writes * (k + m)
    assert c["packets.to_nodes"] == shards * num_packets(
        shard, WriteRequestHeader(0, shard).packed_size())
    assert c["packets.to_clients"] == shards            # one ack a shard
    assert c["auth.verifications"] == c["node.write_done"] == shards
    assert c["node.read_done"] == 0                     # no check read
    assert c["codec.dispatches"] == len(run.ops)
    assert c["codec.stripes"] == writes
    assert c["codec.h2d_bytes"] == writes * k * shard
    assert c["codec.d2h_bytes"] == writes * m * shard
    # spans only inside the window's pieces, none in the rotations
    assert run.program_spans
    for s in run.program_spans:
        assert any(a <= s.t0 <= s.t1 <= b for a, b in window), s.name
    assert sum(s.name == "cluster.write"
               for s in run.program_spans) == len(run.ops)
    assert f"program spans: {len(run.program_spans)} kept, 0 dropped" in out
    per_op = next(line for line in out
                  if line.startswith("counters per operation"))
    assert f"codec.dispatches {1.0!r}" in per_op
    assert wall.installed() is None


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


def _program_spans(planes):
    """The fixture's program spans, as the program's tracer records them."""
    return [Span(n, "", s, e) for n, s, e in _host_notes(planes, PATH_SPANS)]


def test_idle_time_of_the_fixture_goes_to_program_spans(program_planes):
    run = _fixture_run(program_planes)
    charged = reduce.idle_by_program_span(
        run.trace, _program_spans(program_planes), run.window, limit=None)
    names = {n for n, _ in charged}
    assert names <= PATH_SPANS | {reduce.OUTSIDE}
    assert {"dfs.write", "dfs.read", "codec.d2h"} <= names
    idle = sum(t for _, t in charged)
    assert idle + run.trace.busy_s(run.window) == pytest.approx(
        run.elapsed_s, rel=0.01)
    top = reduce.idle_by_program_span(
        run.trace, _program_spans(program_planes), run.window)
    assert top == sorted(charged, key=lambda nt: -nt[1])[:10]


def _merged(intervals):
    """Sorted, merged intervals, by a plain loop."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def test_packet_us_on_the_fixture_by_hand(program_planes):
    run = _fixture_run(program_planes)
    run.program_spans = _program_spans(program_planes)
    run.counters = {"packets.to_nodes": 9000, "packets.to_clients": 1000}
    dfs = sorted((s.t0, s.t1) for s in run.program_spans
                 if s.name in ("dfs.write", "dfs.read"))
    # one client thread: one shard at a time, none inside another
    assert all(e <= s for (_, e), (s, _) in zip(dfs, dfs[1:]))
    by_hand = sum(e - s for s, e in dfs) / 10000 / 1e3
    assert reader("packet_us.write")(run) == pytest.approx(by_hand)
    run.counters = {}
    assert reader("packet_us.read")(run) is None
    run.program_spans = None
    assert reader("packet_us.repair")(run) is None


def test_codec_host_share_on_the_fixture_by_hand(program_planes):
    run = _fixture_run(program_planes)
    run.program_spans = _program_spans(program_planes)
    ((_, ws, we),) = _host_notes(program_planes, {reduce.WINDOW_ANNOTATION})
    ops_ = [(e.start_ns, e.start_ns + e.duration_ns) for p in program_planes
            if p.name == TPU for line in p.lines
            if line.name == reduce.OPS_LINE for e in line.events]
    idle = 0
    for s in run.program_spans:
        if s.name not in ("rs.encode", "rs.decode"):
            continue
        inside = _merged((max(a, s.t0), min(b, s.t1)) for a, b in ops_
                         if a < s.t1 and b > s.t0)
        idle += (s.t1 - s.t0) - sum(b - a for a, b in inside)
    share = reader("codec_host_share.write")(run)
    assert share == pytest.approx(100 * idle / (we - ws))
    assert 0 < share < 100
    run.trace = None
    assert reader("codec_host_share.read")(run) is None


def test_ckpt_snapshot_share_by_hand(program_planes):
    run = _fixture_run(program_planes)
    run.program_spans = _program_spans(program_planes)
    read = reader("ckpt_snapshot_share")
    assert read(run) is None                        # no save in the fixture
    run.program_spans = [Span("ckpt.save", "entry", 0, 100),
                         Span("ckpt.snapshot", "entry", 10, 17),
                         Span("ckpt.leaf", "entry", 20, 90),
                         Span("ckpt.save", "entry", 200, 300),
                         Span("ckpt.snapshot", "entry", 205, 208)]
    run.window = [(0, 1000)]
    assert read(run) == pytest.approx(100 * 10 / 200)
    run.window = [(0, 150)]                         # the first save alone
    assert read(run) == pytest.approx(7)
