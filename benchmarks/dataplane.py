"""RS data-plane throughput sweep: per-stripe loop vs batched fused pipeline.

Sweeps stripe count x chunk size x RS scheme over the bit-sliced kernel
data plane (kernels/ops.py) and emits ``BENCH_dataplane.json`` — the
bytes/s trajectory every future PR regresses against.  Two paths per cell:

  * ``per_stripe``: S separate ``ops.gf_matmul_bytes_batched`` calls of
    one stripe each — one dispatch, pack/unpack round trip, and host sync
    per stripe.  The tile is chosen from the chunk length alone, the same
    as the batched path's, so the ratio isolates batching itself, not
    tile-padding differences;
  * ``batched``: one ``ops.gf_matmul_bytes_batched`` call over all S
    stripes (single fused pack -> bit-sliced matmul -> unpack dispatch
    over the whole (stripe, word-block) grid).

Throughput counts data bytes in (S * k * L) per encode.  On CPU the Pallas
kernel runs in interpret mode, so absolute numbers track the pipeline
shape, not TPU silicon — the per-stripe/batched *ratio* is the regression
signal (see ISSUE/ROADMAP: batched must hold >= 2x at S >= 8).

Usage:
  PYTHONPATH=src python benchmarks/dataplane.py [--out BENCH_dataplane.json]
      [--stripes 1 2 8 16] [--chunk-sizes 4096 65536] [--codes 3,2 6,3 10,4]
      [--repeats 3] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

DEFAULT_CODES = ((3, 2), (6, 3), (10, 4))
DEFAULT_STRIPES = (1, 2, 8, 16)
# The streaming-EC regime the paper's data path serves: MTU-to-chunk-scale
# payloads (section VI; 2 KiB MTU, KiB-scale stripe chunks).  At >= 256 KiB
# chunks the bit-sliced kernel is bandwidth-bound and both paths converge.
DEFAULT_CHUNKS = (1024, 4096, 16384)


def _time(fn, repeats: int) -> float:
    """Best-of-N wall time (s); one untimed warmup to absorb jit tracing."""
    fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def sweep(
    codes=DEFAULT_CODES,
    stripes=DEFAULT_STRIPES,
    chunk_sizes=DEFAULT_CHUNKS,
    repeats: int = 3,
) -> dict:
    from repro.core.erasure import RSCode
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    rows = []
    for k, m in codes:
        parity = RSCode(k, m).parity_matrix
        for chunk in chunk_sizes:
            for s in stripes:
                data = rng.integers(0, 256, (s, k, chunk), dtype=np.uint8)
                nbytes = data.nbytes

                def per_stripe(data=data, parity=parity):
                    for i in range(len(data)):
                        np.asarray(ops.gf_matmul_bytes_batched(
                            parity, data[i:i + 1]))

                def batched(data=data, parity=parity):
                    np.asarray(ops.gf_matmul_bytes_batched(parity, data))

                t_loop = _time(per_stripe, repeats)
                t_batch = _time(batched, repeats)
                rows.append({
                    "code": f"rs{k}_{m}",
                    "k": k,
                    "m": m,
                    "stripes": s,
                    "chunk_bytes": chunk,
                    "data_bytes": nbytes,
                    "per_stripe_us": round(t_loop * 1e6, 1),
                    "batched_us": round(t_batch * 1e6, 1),
                    "per_stripe_bytes_per_s": round(nbytes / t_loop, 1),
                    "batched_bytes_per_s": round(nbytes / t_batch, 1),
                    "speedup": round(t_loop / t_batch, 2),
                })
    import jax

    return {
        "bench": "dataplane",
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "metric": "bytes_per_s",
        "rows": rows,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_dataplane.json",
                    help="JSON artifact path (default: BENCH_dataplane.json)")
    ap.add_argument("--stripes", type=int, nargs="+", default=list(DEFAULT_STRIPES))
    ap.add_argument("--chunk-sizes", type=int, nargs="+",
                    default=list(DEFAULT_CHUNKS))
    ap.add_argument("--codes", nargs="+", default=None,
                    help="RS schemes as k,m pairs (default: 3,2 6,3 10,4)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes for smoke testing")
    args = ap.parse_args()

    codes = DEFAULT_CODES
    if args.codes:
        codes = tuple(tuple(int(x) for x in c.split(",")) for c in args.codes)
    stripes, chunks, repeats = args.stripes, args.chunk_sizes, args.repeats
    if args.quick:
        codes, stripes, chunks, repeats = ((3, 2),), [1, 8], [1024], 1

    result = sweep(codes, tuple(stripes), tuple(chunks), repeats)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    print("code,stripes,chunk_bytes,per_stripe_MBps,batched_MBps,speedup")
    for r in result["rows"]:
        print(f"{r['code']},{r['stripes']},{r['chunk_bytes']},"
              f"{r['per_stripe_bytes_per_s'] / 1e6:.1f},"
              f"{r['batched_bytes_per_s'] / 1e6:.1f},{r['speedup']}")


if __name__ == "__main__":
    main()
