#!/usr/bin/env python3
"""Run a cell with the correctness control or a planted fault in place.

    python3 benchmarks/chip/control.py --workload <cell> --fault control \
        --seconds <s> --seeds <n> [<n> ...]

Every seed runs in this one process, one after the other, each printing
its lines and its result line as ``run.py`` does (``setup_s`` counts
from the process's start, so it grows from run to run).  The benchmark's own
runs never do this: it shows that the check judges a broken data plane
not correct.  Exits 0 when every run came out not correct, 1 otherwise.
Faults are described in ``chipbench/faults.py``.
"""

import argparse
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(_HERE)), "src"))

from chipbench import faults, harness  # noqa: E402


def main(argv=None, allow_cpu=False, overrides=None) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=faults.NAMES)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    results = []
    for seed in args.seeds:
        undo = []
        try:
            results.append(harness.main(
                ["--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds)], allow_cpu=allow_cpu,
                hook=lambda cell: undo.append(
                    faults.install(args.fault, cell.config)),
                overrides=overrides))
        finally:
            for u in undo:
                u()
    caught = sum(not r["correct"] for r in results)
    print(f"{args.fault} on {args.workload}: {caught} of {len(results)} "
          f"runs judged not correct", file=sys.stderr)
    return results


if __name__ == "__main__":
    sys.exit(0 if all(not r["correct"] for r in main()) else 1)
