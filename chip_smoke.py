#!/usr/bin/env python3
"""Chip smoke run: the storage data plane, end to end, on one TPU.

Drives the served path once through the entry points a user calls, with
the Reed-Solomon encode and decode on the chip's Pallas kernels, and
checks every byte against an independent reference:

* Phase A, objects under HDFS's built-in erasure-coding policy
  ``RS-6-3-1024k`` (6 data + 3 parity cells of 1 MiB): a 12-node
  ``StorageCluster`` takes >= 512 MiB of 6 MiB objects through
  ``write_object_bulk``; all parity is checked bit for bit against the
  numpy LUT encode; the objects are read back healthy, read degraded
  after a node holding data shards fails, repaired, audited (zero bytes
  lost) and read once more.
* Phase B, a checkpoint: a >= 512 MiB pytree of bf16 and fp32 arrays
  made on the chip is saved by ``CheckpointManager`` under RS(6, 3) with
  6 MiB stripes, one node fails, and the restore must equal every leaf.

It runs as one process and starts none.  Earlier lines carry the
evidence (device, sizes, wall times of the host packet plane plus the
device, compile seconds, ``tpu_custom_call`` in the dispatched
programs); the last line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

Without a TPU it exits 2 and prints no result.

    python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

MiB = 1 << 20

#: HDFS's built-in policy RS-6-3-1024k: 6 data cells + 3 parity cells.
RS_K, RS_M, CELL = 6, 3, 1 * MiB
NODES = 12
#: checkpoint stripe: one full RS-6-3 cell row per stripe object
STRIPE = RS_K * CELL
PHASE_BYTES = 512 * MiB

WALL = "wall s, host packet plane + device (not a device metric)"


def check(ok: bool, what: str) -> None:
    """Fail the run (exit 1, no result line) unless ``ok``."""
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def device_info(allow_cpu: bool = False) -> dict:
    """The device as JAX reports it; exits 2 unless it is a TPU
    (``allow_cpu`` lets the tests drive the phases in interpret mode)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" and not allow_cpu:
        print(f"chip_smoke: no TPU (JAX found {info}); nothing was run",
              file=sys.stderr)
        raise SystemExit(2)
    return info


class CompileClock:
    """Sums JAX's backend compile time (a load from the persistent cache
    included) and counts compiles and cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self, phase: str) -> str:
        return (f"{phase}: compile {self.seconds:.3f} s over {self.compiles} "
                f"compiles, {self.cache_hits} persistent-cache hits (cumulative)")


def check_kernel_programs(label: str, n: int, k: int, batches, native: bool):
    """Lower every program ``gf_matmul_bytes_batched`` dispatches for an
    (n, k) GF matrix over each (stripes, chunk bytes) batch, print whether
    it calls the Pallas kernel, and on a TPU require that it does."""
    from repro.kernels import ops

    shapes = sorted({(s, length) for stripes, length in batches
                     for s in ops._dispatch_sizes(stripes, (k + n) * length)})
    for s, length in shapes:
        text = ops.gf_matmul_program(n, k, s, length).as_text()
        has = "tpu_custom_call" in text
        print(f"  {label} program ({n}x{k} GF matrix, {s} stripes x "
              f"{length} B): tpu_custom_call={has}")
        check(has or not native, f"{label} program runs the Pallas kernel")


def _cluster(total_bytes: int, cell: int):
    from repro.checkpoint.storage import StorageCluster

    # RS(6,3) puts 9 of the 12 nodes under each stripe: room for the
    # even share of every shard plus one full stripe of slack per node
    shards = -(-total_bytes // (RS_K * cell)) * (RS_K + RS_M)
    capacity = (-(-shards // NODES) + RS_K + RS_M) * cell
    return StorageCluster(NODES, node_capacity=capacity)


def phase_objects(seed: int, total_bytes: int = PHASE_BYTES,
                  cell: int = CELL, native: bool = True) -> dict:
    """Phase A: RS-6-3 objects through write / read / fail / repair."""
    from repro.core.erasure import RSCode
    from repro.kernels import ops

    obj = RS_K * cell
    n = -(-total_bytes // obj)
    rng = np.random.default_rng(seed)
    blobs = np.frombuffer(rng.bytes(n * obj), np.uint8).reshape(n, obj)
    backend = ops.dataplane_backend()
    print(f"phase A: {n} objects x {obj} B = {n * obj} B, RS({RS_K},{RS_M}) "
          f"cell {cell} B, {NODES} nodes, data-plane backend {backend}")
    if native:
        check(backend == "jax", "the data plane resolves to the kernels")
    check_kernel_programs("encode", RS_M, RS_K, [(n, cell)], native)
    cluster = _cluster(n * obj, cell)

    t0 = time.perf_counter()
    layouts = cluster.write_object_bulk(list(blobs), k=RS_K, m=RS_M)
    t_write = time.perf_counter() - t0
    print(f"  write_object_bulk: {n * obj} B in {t_write:.3f} {WALL}")

    t0 = time.perf_counter()
    want = RSCode(RS_K, RS_M).encode_stripes(
        blobs.reshape(n, RS_K, cell), backend="numpy")
    t_ref = time.perf_counter() - t0
    for i, lay in enumerate(layouts):
        check(lay.chunk_len == cell, f"object {i} is one full cell row")
        for p, coord in enumerate(lay.parity_coords):
            got = cluster.client.read(cluster.capability, coord, cell)
            check(np.array_equal(got, want[i, p]),
                  f"object {i} parity {p} equals the numpy LUT encode")
    print(f"  parity: {n * RS_M} cells bit-exact vs "
          f"RSCode({RS_K},{RS_M}).encode_stripes(backend='numpy') "
          f"(host reference encode {t_ref:.3f} s)")

    def read_all(label: str) -> float:
        t0 = time.perf_counter()
        got = cluster.read_objects(layouts)
        dt = time.perf_counter() - t0
        check(all(g == b.tobytes() for g, b in zip(got, blobs)),
              f"{label} read equals what was written")
        print(f"  {label} read: {n * obj} B equal in {dt:.3f} {WALL}")
        return dt

    t_read = read_all("healthy")
    victim = layouts[0].data_coords[0].node
    slots = Counter(j for lay in layouts
                    for j, c in enumerate(lay.data_coords) if c.node == victim)
    cluster.fail_node(victim)
    print(f"  failed node {victim}: it held data shards of "
          f"{sum(slots.values())} objects")
    check_kernel_programs("decode", RS_K, RS_K,
                          [(s, cell) for s in slots.values()], native)
    t_degraded = read_all("degraded")

    t0 = time.perf_counter()
    stats = cluster.repair_node(victim)
    t_repair = time.perf_counter() - t0
    print(f"  repair_node({victim}): {stats['shards']} shards, "
          f"{stats['bytes']} B in {t_repair:.3f} {WALL}")
    audit = cluster.audit()
    print(f"  audit: {audit}")
    check(audit["lost_bytes"] == 0 and stats["unrecoverable"] == 0,
          "audit after repair shows zero bytes lost")
    check(audit["readable_bytes"] == audit["bytes_written"] == n * obj,
          "every written byte is readable after repair")
    t_after = read_all("post-repair")
    return {"bytes": n * obj, "objects": n, "write_s": t_write,
            "read_s": t_read, "degraded_s": t_degraded, "repair_s": t_repair,
            "post_repair_s": t_after, "audit": audit}


def checkpoint_tree(seed: int, total_bytes: int, stripe: int) -> dict:
    """A pytree of bf16 and fp32 leaves made on the default device from
    ``seed``: four whole-stripe weight matrices (~5/8, 1/8, 1/8, 1/8 of
    the bytes) and one fp32 vector of 13/4 stripes plus 12 bytes, whose
    size is no multiple of the stripe."""
    import jax
    import jax.numpy as jnp

    unit = -(-total_bytes // (8 * stripe))          # stripes per 1/8
    rows = stripe // 4096                           # fp32 rows of 1 KiB cols
    spec = {
        "layers/0/w": ((5 * unit * rows, 1024), jnp.float32),
        "layers/0/w_bf16": ((unit * rows, 2048), jnp.bfloat16),
        "layers/1/w": ((unit * rows, 1024), jnp.float32),
        "layers/1/w_bf16": ((unit * rows, 2048), jnp.bfloat16),
        "norm/scale": ((13 * stripe // 16 + 3,), jnp.float32),
    }
    keys = jax.random.split(jax.random.key(seed), len(spec))
    return {name: jax.random.normal(key, shape, dtype)
            for key, (name, (shape, dtype)) in zip(keys, spec.items())}


def phase_checkpoint(seed: int, total_bytes: int = PHASE_BYTES,
                     cell: int = CELL, native: bool = True) -> dict:
    """Phase B: CheckpointManager save, node failure, restore."""
    import jax

    from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy

    stripe = RS_K * cell
    tree = checkpoint_tree(seed, total_bytes, stripe)
    jax.block_until_ready(tree)
    nbytes = sum(x.nbytes for x in tree.values())
    odd = [k for k, x in tree.items() if x.nbytes % stripe]
    print(f"phase B: checkpoint of {len(tree)} leaves, {nbytes} B on "
          f"{next(iter(tree.values())).devices()}, RS({RS_K},{RS_M}) stripe "
          f"{stripe} B; leaves not a stripe multiple: {odd}")
    check(bool(odd), "one leaf is no multiple of the stripe")
    batches = []
    for x in tree.values():
        full, tail = divmod(x.nbytes, stripe)
        batches.append((full, cell))
        if tail:
            batches.append((1, -(-tail // (RS_K * 32)) * 32))
    batches = [b for b in batches if b[0]]
    check_kernel_programs("encode", RS_M, RS_K, batches, native)
    check_kernel_programs("decode", RS_K, RS_K, batches, native)
    cluster = _cluster(nbytes + len(tree) * stripe, cell)
    mgr = CheckpointManager(cluster, CheckpointPolicy(
        k=RS_K, m=RS_M, stripe_bytes=stripe))

    t0 = time.perf_counter()
    mgr.save(1, tree, blocking=True)
    t_save = time.perf_counter() - t0
    print(f"  save: {nbytes} B in {t_save:.3f} {WALL}")

    victim = 0
    cluster.fail_node(victim)
    audit = cluster.audit()
    check(audit["reconstructable_bytes"] > 0 and audit["lost_bytes"] == 0,
          f"node {victim} held data shards, and nothing is lost: {audit}")
    t0 = time.perf_counter()
    restored = mgr.restore(1)
    t_restore = time.perf_counter() - t0
    for name, leaf in tree.items():
        got, want = restored[name], np.asarray(leaf)
        check(got.dtype == want.dtype and got.shape == want.shape
              and got.tobytes() == want.tobytes(),
              f"restored leaf {name} equals the device array")
    print(f"  failed node {victim}; restore: {len(tree)} leaves, {nbytes} B "
          f"bit-exact vs the device arrays in {t_restore:.3f} {WALL}")
    return {"bytes": nbytes, "save_s": t_save, "restore_s": t_restore}


def main(argv: list[str] | None = None, allow_cpu: bool = False,
         total_bytes: int = PHASE_BYTES, cell: int = CELL) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every object and checkpoint leaf")
    args = ap.parse_args(argv)

    info = device_info(allow_cpu)
    import jax

    from repro.bench import setup_compile_cache

    cache = setup_compile_cache()
    print(f"device: {info['platform']} {info['kind']} x{info['count']}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)
    clock = CompileClock()
    native = info["platform"] == "tpu"
    t0 = time.perf_counter()
    phase_objects(args.seed, total_bytes, cell, native)
    print(f"phase A total {time.perf_counter() - t0:.3f} {WALL}")
    print(clock.line("phase A"), flush=True)
    t0 = time.perf_counter()
    phase_checkpoint(args.seed + 1, total_bytes, cell, native)
    print(f"phase B total {time.perf_counter() - t0:.3f} {WALL}")
    print(clock.line("phase B"), flush=True)
    result = {"ok": True, "device": info}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
