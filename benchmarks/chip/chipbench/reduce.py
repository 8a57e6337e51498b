"""Every reduction from a run's records to its numbers, in one module.

Times are integer nanoseconds.  Host spans and operations are on the
``time.perf_counter_ns`` clock; a device trace is moved onto it by the
host annotation the harness opens at the window's start.  Interval
lists are sorted, disjoint ``(start, end)`` pairs unless said otherwise.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import math
import os

#: The host annotation that opens the measured window in a device trace.
WINDOW_ANNOTATION = "chipbench_window"
#: Device trace lines: single operations, and whole XLA programs.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: The jitted codec program (bit-plane pack, Pallas GF matmul, unpack),
#: found by its XLA module name.
CODEC_PROGRAM = "_encode_planes_batched"
#: What device idle time is charged to where no program span is open.
OUTSIDE = "outside the program"


# -- plain statistics ------------------------------------------------------

def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of all values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    return xs[max(0, math.ceil(q / 100 * len(xs)) - 1)]


def rate_MBps(ops, elapsed_s: float) -> float:
    """Bytes of the operations that succeeded, in 10^6 B/s of window."""
    return sum(op.nbytes for op in ops if op.ok) / elapsed_s / 1e6


def latencies_ms(ops) -> list[float]:
    return [(op.end - op.start) / 1e6 for op in ops]


def gf_matmul_bytes(n: int, k: int, stripes: int, length: int) -> int:
    """Bytes an (n, k) GF(2^8) matrix over S stripes of L-byte cells must
    move at the least: read k cells and write n cells per stripe."""
    return stripes * (k + n) * length


# -- intervals -------------------------------------------------------------

def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[int, int]]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, window) -> list[tuple[int, int]]:
    """The parts of ``window`` that ``busy`` leaves uncovered."""
    out = []
    for ws, we in window:
        cur = ws
        for s, e in intersect(busy, [(ws, we)]):
            if s > cur:
                out.append((cur, s))
            cur = e
        if cur < we:
            out.append((cur, we))
    return out


# -- host spans ------------------------------------------------------------

def span_union(spans, category: str, window) -> list[tuple[int, int]]:
    return intersect(union((s.start, s.end) for s in spans
                           if s.category == category), window)


def span_share(spans, category: str, window) -> float | None:
    """Percent of the window during which the host was inside a span of
    ``category``; None when no such span was recorded."""
    if not any(s.category == category for s in spans):
        return None
    return 100 * measure(span_union(spans, category, window)) / measure(window)


def self_share(spans, outer: str, inner: str) -> float | None:
    """Percent of the time inside ``outer`` spans not covered by
    ``inner`` spans (on any thread)."""
    outers = [(s.start, s.end) for s in spans if s.category == outer]
    if not outers:
        return None
    inners = union((s.start, s.end) for s in spans if s.category == inner)
    total = sum(e - s for s, e in outers)
    covered = sum(measure(intersect(inners, [o])) for o in outers)
    return 100 * (total - covered) / total


def in_window(spans, category: str, window):
    """Spans of ``category`` that start inside the window."""
    return [s for s in spans if s.category == category
            and any(ws <= s.start < we for ws, we in window)]


# -- the program's spans ---------------------------------------------------
# ``repro.trace.Span`` records of ``repro.trace.wall``: ``name``, ``t0``,
# ``t1``, on any thread.

def program_union(spans, names, window) -> list[tuple[int, int]]:
    """Where a program span named in ``names`` was open, in the window."""
    return intersect(union((s.t0, s.t1) for s in spans if s.name in names),
                     window)


def last_opened(spans) -> list[tuple[int, int, str]]:
    """``(start, end, name)`` pieces, in order, between every two
    consecutive ends of the spans: in each, the span opened last among
    those open then, on any thread, or :data:`OUTSIDE`."""
    spans = [s for s in spans if s.t1 > s.t0]
    by_start = sorted(spans, key=lambda s: s.t0)
    bounds = sorted({t for s in spans for t in (s.t0, s.t1)})
    heap: list = []         # (-t0, order, span): the last opened on top
    out, j = [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(by_start) and by_start[j].t0 <= a:
            heapq.heappush(heap, (-by_start[j].t0, j, by_start[j]))
            j += 1
        while heap and heap[0][2].t1 <= a:
            heapq.heappop(heap)
        out.append((a, b, heap[0][2].name if heap else OUTSIDE))
    return out


# -- device traces ---------------------------------------------------------

class DeviceTrace:
    """The device events of one profiler trace on the host's
    ``perf_counter_ns`` clock: ``ops[device]`` and ``modules[device]`` are
    lists of ``(name, start, end)``."""

    def __init__(self, ops: dict, modules: dict):
        self.ops, self.modules = ops, modules

    @classmethod
    def from_planes(cls, planes, window_start_ns: int) -> "DeviceTrace":
        """``planes`` as ``jax.profiler.ProfileData`` gives them: the host
        annotation :data:`WINDOW_ANNOTATION` was opened at
        ``window_start_ns`` on the host clock, which fixes the offset."""
        anchor = None
        ops: dict = {}
        modules: dict = {}
        for plane in planes:
            device = plane.name.startswith("/device:")
            for line in plane.lines:
                if device and line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif not device and anchor is None:
                    anchor = next((e.start_ns for e in line.events
                                   if e.name == WINDOW_ANNOTATION), None)
        if anchor is None:
            raise ValueError(f"trace has no {WINDOW_ANNOTATION!r} annotation")
        shift = window_start_ns - anchor

        def moved(per_device):
            return {d: [(n, int(s + shift), int(e + shift)) for n, s, e in evs]
                    for d, evs in per_device.items()}

        return cls(moved(ops), moved(modules))

    @classmethod
    def from_dir(cls, log_dir: str, window_start_ns: int) -> "DeviceTrace":
        import jax

        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise ValueError(f"expected one trace under {log_dir}: {files}")
        planes = list(jax.profiler.ProfileData.from_file(files[0]).planes)
        return cls.from_planes(planes, window_start_ns)

    def busy(self, window) -> dict[str, list[tuple[int, int]]]:
        """Per device, the union of its operations inside the window."""
        return {d: intersect(union((s, e) for _, s, e in evs), window)
                for d, evs in self.ops.items()}

    def busy_s(self, window) -> float | None:
        """Seconds in which an operation ran, averaged over devices."""
        busy = self.busy(window)
        if not busy:
            return None
        return sum(measure(b) for b in busy.values()) / len(busy) / 1e9

    def program_s(self, name: str, window) -> float:
        """Summed device seconds of the programs whose module name holds
        ``name``, counted where they overlap the window."""
        return sum(measure(intersect([(s, e)], window))
                   for evs in self.modules.values()
                   for n, s, e in evs if name in n) / 1e9

    def top_ops(self, window, limit: int = 10) -> list[list]:
        """The device operations that took most time in the window, each
        named ``<program>/<instruction>`` (the HLO instruction's name,
        without its operands)."""
        total: dict[str, int] = {}
        for dev, evs in self.ops.items():
            mods = sorted(self.modules.get(dev, []), key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for n, s, e in evs:
                t = measure(intersect([(s, e)], window))
                if not t:
                    continue
                at = bisect.bisect_right(starts, s) - 1
                prog = mods[at][0].split("(")[0] if at >= 0 and \
                    s < mods[at][2] else "?"
                key = f"{prog}/{n.split(' = ')[0]}"
                total[key] = total.get(key, 0) + t
        top = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
        return [[n, t / 1e9] for n, t in top]


def idle_by_program_span(trace: DeviceTrace, spans, window,
                         limit: int | None = 10) -> list[list]:
    """Idle time of the device in the window, charged to what the host
    was doing: the program span opened last among those open at the
    time (:func:`last_opened`), else :data:`OUTSIDE`.  Averaged over
    devices; the ``limit`` largest, largest first."""
    busy = trace.busy(window)
    if not busy:
        return []
    pieces = last_opened(spans)
    charged: dict[str, int] = {}
    for dev_busy in busy.values():
        for s, e in gaps(dev_busy, window):
            # the pieces overlapping the gap, and outside them OUTSIDE
            at = max(0, bisect.bisect_right(pieces, (s,)) - 1)
            rest = e - s
            while at < len(pieces) and pieces[at][0] < e:
                a, b, name = pieces[at]
                t = min(b, e) - max(a, s)
                if t > 0:
                    charged[name] = charged.get(name, 0) + t
                    rest -= t
                at += 1
            if rest:
                charged[OUTSIDE] = charged.get(OUTSIDE, 0) + rest
    n = len(busy)
    top = sorted(charged.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, t / n / 1e9] for name, t in top]
