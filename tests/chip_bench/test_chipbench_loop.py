"""The measured window's loop, as the traffic file sets it: closed, or
open at a fixed rate, with a stand-in cell whose operations take a set
time."""

import itertools
import time

import pytest

from chipbench import harness, reduce


class Sleeper:
    """Operations that take ``service_s`` each; never full."""

    def __init__(self, service_s: float):
        self.service_s = service_s
        self.calls = 0

    def full(self) -> bool:
        return False

    def op(self, i: int) -> int:
        assert i == self.calls
        self.calls += 1
        time.sleep(self.service_s)
        return 100


def test_closed_loop_ends_at_the_first_completion_after_its_seconds():
    ops, window = harness.measure_window(Sleeper(0.03), 0.2, None)
    assert harness.arrivals({"kind": "closed"}, 1) is None
    assert len(window) == 1 and window[0][1] == ops[-1].end
    assert reduce.measure(window) >= 0.2e9
    assert ops[-2].end - window[0][0] < 0.2e9
    for a, b in zip(ops, ops[1:]):
        assert b.start >= a.end


def open_loop(rate: float) -> dict:
    return {"kind": "open", "rate_per_s": rate}


def test_open_loop_starts_each_operation_at_its_arrival():
    due = [t for t in itertools.islice(harness.arrivals(open_loop(50), 1), 60)
           if t < 0.3e9]
    ops, window = harness.measure_window(
        Sleeper(0.002), 0.3, None, harness.arrivals(open_loop(50), 1))
    t0 = window[0][0]
    assert [op.start - t0 for op in ops] == due
    assert all(op.end - op.start >= 2e6 for op in ops)
    # idle at the end: the window closes at its seconds
    assert 0.3 <= reduce.measure(window) / 1e9 < 0.35


def test_open_loop_over_capacity_counts_the_queue_in_each_latency():
    ops, _ = harness.measure_window(Sleeper(0.02), 0.3, None,
                                    harness.arrivals(open_loop(200), 1))
    lat = reduce.latencies_ms(ops)
    # 200 arrivals a second against 50 served: the queue grows
    assert len(ops) < 0.3 * 200 / 2
    assert lat[-1] > lat[0] + 5 * (len(lat) - 2)
    for a, b in zip(ops, ops[1:]):
        assert b.start <= a.end


def test_poisson_arrivals_are_drawn_from_the_seed():
    def first(seed, n=2000):
        due = harness.arrivals(open_loop(1000), seed)
        return [next(due) for _ in range(n)]

    a, b = first(2**31 + 7), first(2**31 + 7)
    assert a == b and a != first(2**31 + 8)
    assert all(y > x for x, y in zip(a, a[1:]))
    assert a[-1] / len(a) == pytest.approx(1e6, rel=0.1)


def test_unknown_loop_is_refused():
    with pytest.raises(ValueError):
        harness.arrivals({"kind": "sometimes"}, 1)
