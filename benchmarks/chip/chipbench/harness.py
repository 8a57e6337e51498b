"""One run of one cell: set-up, the measured window, the check, the
result line.  ``BENCHMARK.json`` names the cell's configuration and
traffic files and its metrics; each metric is read by
``metrics/<name>.py``, or, for a name with a dot, by the file of the
part before the first dot, whose ``read(run)`` returns the number or
None when the run holds nothing to read.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import itertools
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

from chipbench import cells, reduce, spans
from chipbench.cache import CHECKOUT, CompileClock, setup_compile_cache

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: failed operations whose traceback is printed; the rest are counted
TRACEBACKS = 3
#: program spans a traced window keeps (the save cell's 40 s window on a
#: v5e holds about 210,000): room for a program several times faster
SPAN_BUFFER = 4_000_000


def process_age_s() -> float | None:
    """Seconds since this process started, from ``/proc`` (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return None
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


@dataclasses.dataclass(frozen=True)
class Op:
    start: int
    end: int
    nbytes: int
    ok: bool


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""

    ops: list
    #: the measured window: (start, end) pieces, perf_counter_ns
    window: list
    setup_s: float
    #: the benchmark's spans around calls into the program's layers
    spans: list
    trace: reduce.DeviceTrace | None
    device_kind: str
    #: the program's own spans (``repro.trace.Span``: ``name``, ``t0``,
    #: ``t1`` on the window's clock) inside the window; None when none
    #: were recorded or the tracer dropped some
    program_spans: list | None = None
    #: the cell's counters (:meth:`chipbench.cells.Cell.counters`):
    #: name -> sum over the window's pieces
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def elapsed_s(self) -> float:
        return reduce.measure(self.window) / 1e9

    def peak(self, key: str) -> float:
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if self.device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in peaks.json")
        return peaks[self.device_kind][key]


def load_bench(root: str = CHECKOUT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic) of a cell, by name."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(CHECKOUT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return entry, config, traffic


def cell_metrics(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The end-to-end metrics the cell reports, or with ``traced`` its
    per-layer metrics (those without ``workloads`` go to every cell that
    reports the end-to-end metric they move)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def reader(name: str):
    """``read`` of ``metrics/<name>.py``, else of the file named by the
    part of ``name`` before its first dot."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in metrics/")


def device_info(chips: int, allow_cpu: bool) -> dict:
    """The devices as JAX reports them; exits 2 unless they are TPUs and
    at least ``chips`` of them (``allow_cpu`` lets tests drive a run)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not allow_cpu and (info["platform"] != "tpu" or len(devs) < chips):
        print(f"chipbench: needs {chips} TPU chip(s), JAX found {info}; "
              f"nothing was run", file=sys.stderr)
        raise SystemExit(2)
    return info


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return max(peaks)


def arrivals(loop: dict, seed: int):
    """The traffic file's ``loop``: None for a closed loop of one client
    (``{"kind": "closed"}``, the default); for an open loop
    (``{"kind": "open", "rate_per_s": r}``) an endless iterator of
    Poisson arrival times at the fixed rate ``r``, in ns of window
    clock, drawn from the seed."""
    kind = loop.get("kind", "closed")
    if kind == "closed":
        return None
    if kind != "open":
        raise ValueError(f"unknown loop {kind!r}: closed or open")
    gap = 1e9 / float(loop["rate_per_s"])
    rng = np.random.default_rng([seed, 1])
    return (int(t) for t in itertools.accumulate(
        rng.exponential(gap) for _ in itertools.count()))


class Recorder:
    """What a ``--trace 1`` run records inside the window's pieces: the
    benchmark's spans around the program's layers, the program's own
    spans (a ``repro.trace.Tracer.wall()`` installed) and the cell's
    counters, summed over the pieces as differences, each piece read on
    the registry of the cluster it drove.  As a context manager it
    records from entry to exit; :meth:`pause` and :meth:`resume` leave
    out the retirement of a full cluster."""

    def __init__(self, cell):
        from repro.trace import Tracer

        self.cell = cell
        self.store = spans.SpanStore()
        self.tracer = Tracer.wall(max_spans=SPAN_BUFFER)
        self.counters: dict = {}
        self._opened = None

    def resume(self) -> None:
        from repro.trace import wall

        reg = self.cell.counters()
        self._opened = (reg, reg.snapshot())
        self.store.enabled = True
        wall.install(self.tracer)

    def pause(self) -> None:
        from repro.trace import wall

        wall.uninstall()
        self.store.enabled = False
        reg, before = self._opened
        for name, d in reg.diff(before, reg.snapshot()).items():
            self.counters[name] = self.counters.get(name, 0) + d

    def __enter__(self) -> "Recorder":
        self.resume()
        return self

    def __exit__(self, *exc) -> bool:
        self.pause()
        return False

    def program_spans(self) -> list | None:
        return None if self.tracer.dropped else self.tracer.spans


def measure_window(cell, seconds: float, recorder=None, due=None
                   ) -> tuple[list, list]:
    """One client serves operations until the first one that completes
    after ``seconds`` of window.  Closed loop (``due`` None): back to
    back.  Open loop: operation ``i`` arrives at the ``i``-th time of
    ``due``; it starts then, or when the one before it ends if that is
    later, and its latency counts from its arrival; the window ends at
    ``seconds`` when no operation is in flight then.  The clock stops
    while a full cluster is retired and checked (an arrival due before
    that counts from the restart), and so does ``recorder`` (a
    :class:`Recorder`, in a traced run)."""
    ops, window, failures = [], [], 0
    limit = int(seconds * 1e9)
    piece = time.perf_counter_ns()
    done_ns = 0         # window clock at the start of this piece
    i = 0
    while True:
        if cell.full():
            now = time.perf_counter_ns()
            window.append((piece, now))
            done_ns += now - piece
            if recorder is not None:
                recorder.pause()
            cell.rotate()
            if recorder is not None:
                recorder.resume()
            piece = time.perf_counter_ns()
        if due is None:
            start = time.perf_counter_ns()
        else:
            at = min(next(due), limit)
            ahead = at - (done_ns + time.perf_counter_ns() - piece)
            if ahead > 0:
                time.sleep(ahead / 1e9)
            start = piece + max(0, at - done_ns)
            if at == limit:
                window.append((piece, max(start, time.perf_counter_ns())))
                return ops, window
        try:
            nbytes, ok = cell.op(i), True
        except Exception:  # a failed operation is counted, not fatal
            nbytes, ok = 0, False
            failures += 1
            if failures <= TRACEBACKS:
                traceback.print_exc()
        end = time.perf_counter_ns()
        ops.append(Op(start, end, nbytes, ok))
        i += 1
        if done_ns + end - piece >= limit:
            window.append((piece, end))
            return ops, window


@contextlib.contextmanager
def profiled(log_dir: str | None):
    """The profiler on around the window, which opens with the host
    annotation that ties the device trace to the host clock; nothing
    when ``log_dir`` is None."""
    if log_dir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(reduce.WINDOW_ANNOTATION):
            yield
    finally:
        jax.profiler.stop_trace()


def main(argv: list[str] | None = None, allow_cpu: bool = False,
         hook=None, overrides: dict | None = None) -> dict:
    """One run; prints its lines and returns the result.  ``hook(cell)``
    runs before the cell's set-up (the control and the planted faults use
    it); ``overrides`` updates keys of the configuration and traffic
    files (tests run tiny sizes)."""
    age = process_age_s()
    t_start = time.perf_counter() - (age if age is not None else 0.0)
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_bench()
    entry, config, traffic = cell_files(bench, args.workload)
    config.update((overrides or {}).get("config", {}))
    traffic.update((overrides or {}).get("traffic", {}))
    info = device_info(entry["chips"], allow_cpu)
    t_devices = time.perf_counter()
    cache = setup_compile_cache()
    clock = CompileClock()
    print(f"cell {args.workload}: seed {args.seed}, {args.seconds} s, "
          f"trace {args.trace}; device {info}; compile cache {cache}",
          flush=True)

    cell = cells.make(config, traffic, args.seed)
    if hook is not None:
        hook(cell)
    cell.setup()
    phases = [("start to JAX's devices", t_devices - t_start)] + cell.phases
    print("set-up phases: " + ", ".join(f"{n} {t:.3f} s" for n, t in phases),
          flush=True)

    recorder = Recorder(cell) if args.trace else None
    trace = None
    with contextlib.ExitStack() as stack:
        log_dir = None
        if recorder is not None:
            stack.callback(spans.install(recorder.store))
            log_dir = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="chipbench-trace-"))
        with profiled(log_dir):
            c0 = clock.snapshot()
            setup_s = time.perf_counter() - t_start
            with recorder or contextlib.nullcontext():
                ops, window = measure_window(
                    cell, args.seconds, recorder,
                    arrivals(traffic.get("loop", {}), args.seed))
            c1 = clock.snapshot()
            peak = memory_peak_bytes(entry["chips"])
        if log_dir is not None and info["platform"] == "tpu":
            trace = reduce.DeviceTrace.from_dir(log_dir, window[0][0])
    lat = reduce.latencies_ms(ops)
    print("operation latencies, ms: " + ", ".join(
        f"p{q} {reduce.percentile(lat, q):.3f}" for q in (0, 50, 95, 100)),
        flush=True)
    print(f"window: {len(ops)} operations in {reduce.measure(window) / 1e9:.6f} s"
          f" ({len(window)} piece(s)); compiles inside it: {c1[1] - c0[1]} "
          f"({c1[0] - c0[0]:.3f} s), persistent-cache hits inside it: "
          f"{c1[2] - c0[2]}; set-up {setup_s:.3f} s with {c0[1]} compiles "
          f"({c0[0]:.3f} s) and {c0[2]} cache hits; device peak_bytes_in_use "
          f"{peak}", flush=True)

    t_check = time.perf_counter()
    checks = cell.check()
    print(f"check: {time.perf_counter() - t_check:.3f} s", flush=True)
    failed = sum(not op.ok for op in ops)
    checks["failed_operations"] = (failed, 0)

    run = Run(ops, window, setup_s, [], trace, info["kind"])
    if recorder is not None:
        run.spans = recorder.store.spans
        run.program_spans = recorder.program_spans()
        run.counters = recorder.counters
        print(f"program spans: {len(recorder.tracer.spans)} kept, "
              f"{recorder.tracer.dropped} dropped"
              + ("; readers of program spans report nothing"
                 if run.program_spans is None else ""), flush=True)
    metrics = {}
    for m in cell_metrics(bench, args.workload, bool(args.trace)):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(info, memory_peak_bytes=peak)
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": len(ops), "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s(window)
        device["window_s"] = run.elapsed_s
        result["breakdown"] = {
            "device_ops": trace.top_ops(window),
            "idle_gaps": [] if run.program_spans is None else
            reduce.idle_by_program_span(trace, run.program_spans, window)}
    if recorder is not None:
        print(f"counters per operation ({len(ops)} operations): "
              + ", ".join(f"{name} {v / max(1, len(ops))!r}"
                          for name, v in sorted(run.counters.items()) if v),
              flush=True)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result
