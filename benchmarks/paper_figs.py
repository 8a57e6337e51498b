"""Paper-figure benchmark functions (one per table/figure).

Each returns a list of CSV rows: (name, us_per_call, derived) where
``us_per_call`` is the simulated or measured latency and ``derived`` is the
figure-specific metric (ratio vs. baseline, GB/s, ...).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.packets import ReplStrategy
from repro.core.state import (
    WRITE_DESCRIPTOR_BYTES,
    descriptor_memory_budget,
    littles_law_concurrent_writes,
    max_concurrent_writes,
)
from repro.sim import protocols as P
from repro.sim.network import NetConfig
from repro.sim.pspin import HANDLER_NS, PsPINConfig, handler_budget_ns, hpus_for_line_rate

KiB = 1024
SIZES = [1 * KiB, 4 * KiB, 16 * KiB, 64 * KiB, 256 * KiB, 512 * KiB]


def fig6_write_latency() -> list[tuple]:
    """Fig. 6: write latency by protocol and size (derived = vs raw)."""
    rows = []
    for size in SIZES:
        raw = P.run_raw_write(size).latency_ns / 1e3
        for name, fn in [
            ("raw", P.run_raw_write),
            ("sPIN", P.run_spin_auth_write),
            ("RPC", P.run_rpc_write),
            ("RPC+RDMA", P.run_rpc_rdma_write),
        ]:
            us = fn(size).latency_ns / 1e3
            rows.append((f"fig6/{name}/{size // KiB}KiB", round(us, 2),
                         round(us / raw, 3)))
    return rows


def fig7_pspin_breakdown() -> list[tuple]:
    """Fig. 7: packet-processing overheads in PsPIN (2 KiB packet)."""
    cfg = PsPINConfig()
    rows = [
        ("fig7/buffer_copy", cfg.buffer_copy_cycles_2k / cfg.ghz / 1e3, 32),
        ("fig7/scheduling", cfg.sched_cycles / cfg.ghz / 1e3, 2),
        ("fig7/l1_copy", cfg.l1_copy_cycles_2k / cfg.ghz / 1e3, 43),
        ("fig7/hpu_sched", cfg.hpu_sched_ns / 1e3, 1),
        ("fig7/validate_handler", HANDLER_NS["auth"][0] / 1e3, 211),
    ]
    return [(n, round(us, 4), d) for n, us, d in rows]


def fig9_replication_latency() -> list[tuple]:
    """Fig. 9 left/center: replication latency, k=2 and k=4."""
    rows = []
    for k in (2, 4):
        for size in SIZES:
            runners = {
                "RDMA-Flat": lambda: P.run_rdma_flat(size, k),
                "HyperLoop": lambda: P.run_hyperloop(size, k),
                "CPU-Ring": lambda: P.run_cpu_ring(size, k),
                "CPU-PBT": lambda: P.run_cpu_pbt(size, k),
                "sPIN-Ring": lambda: P.run_spin_replication(
                    size, k, ReplStrategy.RING),
                "sPIN-PBT": lambda: P.run_spin_replication(
                    size, k, ReplStrategy.PBT),
            }
            lats = {n: f().latency_ns / 1e3 for n, f in runners.items()}
            best_alt = min(v for n, v in lats.items() if not n.startswith("sPIN"))
            best_spin = min(v for n, v in lats.items() if n.startswith("sPIN"))
            for n, v in lats.items():
                rows.append((f"fig9/k{k}/{n}/{size // KiB}KiB", round(v, 2),
                             round(best_alt / best_spin, 3)))
    return rows


def fig9_goodput() -> list[tuple]:
    """Fig. 9 right: single-node ingest goodput (GB/s; line rate 50)."""
    rows = []
    for size in [1 * KiB, 2 * KiB, 4 * KiB, 8 * KiB, 16 * KiB, 64 * KiB]:
        for strat, name in [(ReplStrategy.RING, "ring"),
                            (ReplStrategy.PBT, "pbt")]:
            g = P.run_spin_goodput(size, 4, strat, num_writes=96)
            rows.append((f"fig9r/{name}/{size // KiB}KiB", 0.0, round(g, 2)))
    return rows


def fig10_vary_k() -> list[tuple]:
    """Fig. 10: latency vs replication factor (4 KiB and 512 KiB)."""
    rows = []
    for size in (4 * KiB, 512 * KiB):
        for k in (2, 3, 4, 6, 8):
            flat = P.run_rdma_flat(size, k).latency_ns / 1e3
            ring = P.run_spin_replication(size, k, ReplStrategy.RING).latency_ns / 1e3
            pbt = P.run_spin_replication(size, k, ReplStrategy.PBT).latency_ns / 1e3
            rows += [
                (f"fig10/{size // KiB}KiB/k{k}/RDMA-Flat", round(flat, 2),
                 round(flat / ring, 2)),
                (f"fig10/{size // KiB}KiB/k{k}/sPIN-Ring", round(ring, 2), 1.0),
                (f"fig10/{size // KiB}KiB/k{k}/sPIN-PBT", round(pbt, 2),
                 round(pbt / ring, 2)),
            ]
    return rows


def table1_handler_stats() -> list[tuple]:
    """Table I: handler durations (measured compute + emergent stalls)."""
    rows = []
    for key, label in [("auth", "k=1"), ("repl_ring", "k=4,Ring"),
                       ("repl_pbt", "k=4,PBT")]:
        hh, ph, ch = HANDLER_NS[key]
        rows += [
            (f"table1/{label}/HH", round(hh / 1e3, 3), hh),
            (f"table1/{label}/PH", round(ph / 1e3, 3), ph),
            (f"table1/{label}/CH", round(ch / 1e3, 3), ch),
        ]
    # emergent under load:
    pbt = P.run_spin_replication(8 * KiB, 4, ReplStrategy.PBT, num_writes=96)
    rows.append(("table1/k=4,PBT/mean_loaded",
                 round(pbt.extra["mean_handler_ns"] / 1e3, 3),
                 round(pbt.extra["mean_handler_ns"], 1)))
    return rows


def fig15_erasure() -> list[tuple]:
    """Fig. 15: EC encode latency (RS(3,2)) + bandwidth (RS(6,3)) at
    100 Gbit/s (INEC's testbed speed)."""
    cfg = NetConfig(bandwidth_gbps=100.0)
    rows = []
    for block in SIZES:
        sp = P.run_spin_triec(block, 3, 2, cfg=cfg).latency_ns / 1e3
        inec = P.run_inec_triec(block, 3, 2, cfg=cfg).latency_ns / 1e3
        rows += [
            (f"fig15/lat/sPIN-TriEC/{block // KiB}KiB", round(sp, 2),
             round(inec / sp, 2)),
            (f"fig15/lat/INEC-TriEC/{block // KiB}KiB", round(inec, 2), 1.0),
        ]
    for block, nb in [(1 * KiB, 96), (16 * KiB, 48), (64 * KiB, 24),
                      (512 * KiB, 12)]:
        bs = P.run_spin_triec(block, 6, 3, cfg=cfg, num_blocks=nb).extra[
            "bandwidth_GBps"]
        bi = P.run_inec_triec(block, 6, 3, cfg=cfg, num_blocks=nb).extra[
            "bandwidth_GBps"]
        rows += [
            (f"fig15/bw/sPIN-TriEC/{block // KiB}KiB", 0.0, round(bs, 3)),
            (f"fig15/bw/INEC-TriEC/{block // KiB}KiB", 0.0, round(bi, 3)),
            (f"fig15/bw/ratio/{block // KiB}KiB", 0.0, round(bs / bi, 1)),
        ]
    return rows


def table2_fig16_ec_handlers() -> list[tuple]:
    """Table II + Fig. 16: EC handler durations and HPU scaling."""
    rows = []
    for key, label in [("ec_data_rs32", "RS(3,2)"), ("ec_data_rs63", "RS(6,3)")]:
        hh, ph, ch = HANDLER_NS[key]
        rows += [
            (f"table2/{label}/PH", round(ph / 1e3, 3), ph),
        ]
        for rate in (400.0, 200.0):
            rows.append(
                (f"fig16/{label}/hpus@{int(rate)}G", 0.0,
                 hpus_for_line_rate(ph, rate))
            )
    rows.append(("fig16/budget@400G/32hpu",
                 round(handler_budget_ns(400.0) / 1e3, 3),
                 round(handler_budget_ns(400.0), 1)))
    return rows


def fig4_nic_memory() -> list[tuple]:
    """Fig. 4: worst-case NIC memory vs concurrent writes (Little's law)."""
    rows = [
        ("fig4/descriptor_bytes", 0.0, WRITE_DESCRIPTOR_BYTES),
        ("fig4/budget_MiB", 0.0, round(descriptor_memory_budget() / 2**20, 1)),
        ("fig4/max_concurrent_writes", 0.0, max_concurrent_writes()),
    ]
    for size in (512, 2048, 8192, 65536):
        n = littles_law_concurrent_writes(size, 2e-6)
        mem = n * WRITE_DESCRIPTOR_BYTES
        rows.append((f"fig4/inflight@{size}B", 0.0, round(n, 1)))
        rows.append((f"fig4/mem@{size}B_KiB", 0.0, round(mem / 1024, 2)))
    return rows


def bench_kernels_throughput() -> list[tuple]:
    """GF(2^8) encode throughput: numpy LUT vs the bit-sliced kernel path,
    per-stripe loop vs the batched fused pipeline (derived = GB/s).

    (CPU numbers are for tracking only; the Pallas kernel targets TPU and
    is validated in interpret mode by tests/test_kernels.py.  The full
    stripe x chunk x scheme sweep with its JSON artifact lives in
    benchmarks/dataplane.py.)
    """
    from repro.core.erasure import RSCode
    from repro.kernels import ops

    rows = []
    rng = np.random.default_rng(0)
    for (k, m) in [(3, 2), (6, 3)]:
        code = RSCode(k, m)
        data = rng.integers(0, 256, (k, 1 << 20), dtype=np.uint8)
        t0 = time.perf_counter()
        for _ in range(3):
            code.encode(data, backend="numpy")
        dt = (time.perf_counter() - t0) / 3
        rows.append(
            (f"kernel/rs{k}{m}/numpy_LUT", round(dt * 1e6, 1),
             round(data.nbytes / dt / 1e9, 3))
        )
        # Bit-sliced data plane: 8 concurrent 4 KiB-chunk stripes, the
        # per-stripe loop vs one fused batched dispatch (both with the
        # adaptive tile, so the ratio isolates batching).
        batch = rng.integers(0, 256, (8, k, 4096), dtype=np.uint8)
        parity = code.parity_matrix
        for name, fn in [
            ("loop", lambda b=batch: [np.asarray(
                ops.gf_matmul_bytes_batched(parity, b[i:i + 1]))
                for i in range(len(b))]),
            ("batched", lambda b=batch: np.asarray(
                ops.gf_matmul_bytes_batched(parity, b))),
        ]:
            fn()  # warmup (jit trace)
            t0 = time.perf_counter()
            for _ in range(3):
                fn()
            dt = (time.perf_counter() - t0) / 3
            rows.append(
                (f"kernel/rs{k}{m}/bitsliced_{name}_S8", round(dt * 1e6, 1),
                 round(batch.nbytes / dt / 1e9, 3))
            )
    return rows


ALL_BENCHES = [
    fig6_write_latency,
    fig7_pspin_breakdown,
    fig9_replication_latency,
    fig9_goodput,
    fig10_vary_k,
    table1_handler_stats,
    fig15_erasure,
    table2_fig16_ec_handlers,
    fig4_nic_memory,
    bench_kernels_throughput,
]
