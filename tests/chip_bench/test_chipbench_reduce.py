"""The arithmetic behind every number: percentiles over all operations,
rates over the elapsed window, roofline bytes from shapes, interval
unions, span shares, and the reduction of a recorded TPU trace (three
RS(6,3) encodes of one 6 MiB stripe on a v5e, in
``data/encode_s1.xplane.pb``)."""

import os

import pytest

from chipbench import reduce
from chipbench.harness import Op, Run
from chipbench.spans import Span

TRACE_DIR = os.path.join(os.path.dirname(__file__), "data")
#: the three ``jit__encode_planes_batched`` modules of the recorded trace
ENCODE_NS = [6621143, 6620424, 6623519]
#: where the recorded trace's window annotation starts, in trace time
ANNOTATION_NS = 47116010


@pytest.mark.parametrize("values,q,want", [
    ([5, 1, 4, 2, 3], 95, 5),
    ([5, 1, 4, 2, 3], 50, 3),
    (list(range(1, 201)), 95, 190),
    (list(range(1, 201)), 100, 200),
    ([7.5], 95, 7.5),
])
def test_percentile_is_nearest_rank_over_all_values(values, q, want):
    assert reduce.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        reduce.percentile([], 95)


def test_rate_counts_succeeded_bytes_over_the_elapsed_window():
    ops = [Op(0, 10, 6_000_000, True), Op(10, 20, 6_000_000, False),
           Op(20, 30, 3_000_000, True)]
    assert reduce.rate_MBps(ops, 2.0) == pytest.approx(4.5)
    assert reduce.latencies_ms(ops) == [1e-5, 1e-5, 1e-5]


@pytest.mark.parametrize("n,k,s,length,want", [
    (3, 6, 1, 1 << 20, 9 << 20),        # RS(6,3) encode of one stripe
    (6, 6, 7, 1 << 20, 84 << 20),       # a 6x6 decode of 7 stripes
    (3, 6, 4, 262144, 9 << 20),         # a checkpoint's tail cells
])
def test_gf_matmul_least_bytes_from_shapes(n, k, s, length, want):
    assert reduce.gf_matmul_bytes(n, k, s, length) == want


def test_union_intersect_gaps():
    u = reduce.union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)])
    assert u == [(0, 4), (5, 10)]
    assert reduce.measure(u) == 9
    assert reduce.intersect(u, [(3, 6), (8, 20)]) == [(3, 4), (5, 6), (8, 10)]
    assert reduce.gaps(u, [(-2, 12)]) == [(-2, 0), (4, 5), (10, 12)]
    assert reduce.gaps([], [(0, 5)]) == [(0, 5)]


def _spans():
    return [Span("save", "CheckpointManager.save", 0, 100),
            Span("cluster", "StorageCluster.write_object_bulk", 20, 90),
            Span("packet", "DFSClient.write", 30, 50),
            Span("packet", "DFSClient.write", 40, 70),
            Span("codec", "RSCode.encode_stripes", 22, 28),
            Span("gf", "ops.gf_matmul_bytes_batched", 23, 27, 9 << 20)]


def test_span_shares_take_the_union_inside_the_window():
    spans = _spans()
    assert reduce.span_share(spans, "packet", [(0, 100)]) == 40
    assert reduce.span_share(spans, "packet", [(0, 50), (60, 100)]) == (
        pytest.approx(100 * 30 / 90))
    assert reduce.span_share(spans, "nothing", [(0, 100)]) is None
    assert reduce.self_share(spans, "save", "cluster") == 30
    assert [s.nbytes for s in reduce.in_window(spans, "gf", [(0, 25)])] == [
        9 << 20]
    assert reduce.in_window(spans, "gf", [(24, 100)]) == []


def test_recorded_trace_reduces_to_its_codec_program_and_busy_time():
    start = 10**12                      # any host-clock instant
    trace = reduce.DeviceTrace.from_dir(TRACE_DIR, start)
    assert list(trace.modules) == ["/device:TPU:0"]
    mods = trace.modules["/device:TPU:0"]
    assert [e - s for _, s, e in mods] == ENCODE_NS
    assert all(n.startswith("jit__encode_planes_batched(") for n, _, _ in mods)
    # the offset puts the annotation at the given host instant
    assert mods[0][1] == start + 49125771 - ANNOTATION_NS
    everything = [(start - 10**10, start + 10**10)]
    assert trace.program_s(reduce.CODEC_PROGRAM, everything) == pytest.approx(
        sum(ENCODE_NS) / 1e9)
    assert trace.program_s("no_such_program", everything) == 0
    busy = trace.busy_s(everything)
    # the operations of a program run inside it, with gaps between them
    assert 0.5 * sum(ENCODE_NS) / 1e9 < busy <= sum(ENCODE_NS) / 1e9
    # a window around the second program alone
    _, s, e = mods[1]
    assert trace.program_s(reduce.CODEC_PROGRAM, [(s, e)]) == pytest.approx(
        ENCODE_NS[1] / 1e9)
    top = trace.top_ops(everything)
    assert 1 <= len(top) <= 10
    assert top == sorted(top, key=lambda nt: -nt[1])
    assert sum(t for _, t in top) <= busy + 1e-9


def test_idle_gaps_go_to_the_innermost_host_span():
    """Each idle instant goes to the program span opened last among
    those open then, on any thread."""
    from repro.trace import Span as ProgramSpan

    trace = reduce.DeviceTrace({"/device:TPU:0": [("op", 10, 20),
                                                  ("op", 60, 70)]}, {})
    spans = [ProgramSpan("cluster.read", "entry", 0, 100),
             ProgramSpan("dfs.read", "packet", 25, 55),
             ProgramSpan("rs.decode", "coding", 5, 25),
             # another thread: opened last from 50 to 58
             ProgramSpan("ckpt.leaf", "entry", 50, 58),
             ProgramSpan("empty", "entry", 30, 30)]
    got = dict(reduce.idle_by_program_span(trace, spans, [(0, 100)]))
    # idle: 0-10, 20-60, 70-100; rs.decode takes 5-10 and 20-25,
    # dfs.read 25-50, ckpt.leaf 50-58, cluster.read what is left
    assert got == {"rs.decode": 10e-9, "dfs.read": 25e-9,
                   "ckpt.leaf": 8e-9, "cluster.read": 37e-9}
    got = dict(reduce.idle_by_program_span(trace, spans, [(0, 120)]))
    assert got[reduce.OUTSIDE] == 20e-9
    got = dict(reduce.idle_by_program_span(trace, [], [(0, 100)]))
    assert got == {reduce.OUTSIDE: 80e-9}
    assert reduce.idle_by_program_span(reduce.DeviceTrace({}, {}), spans,
                                       [(0, 100)]) == []


def test_codec_roofline_reader_from_spans_and_trace():
    from chipbench.harness import reader

    trace = reduce.DeviceTrace(
        {}, {"/device:TPU:0": [("jit__encode_planes_batched(1)", 0, 1_000_000),
                               ("jit_other(2)", 0, 5_000_000)]})
    spans = [Span("gf", "ops.gf_matmul_bytes_batched", 0, 10, 819_000)]
    run = Run([], [(0, 2_000_000)], 1.0, spans, trace, "TPU v5 lite")
    # 819 kB at 819 GB/s is 1 us of least time against 1 ms of program
    assert reader("codec_roofline.write")(run) == pytest.approx(0.1)
    run.trace = None
    assert reader("codec_roofline.write")(run) is None
    unknown = Run([], [(0, 2_000_000)], 1.0, spans, trace, "TPU v9")
    with pytest.raises(KeyError):
        reader("codec_roofline.write")(unknown)
