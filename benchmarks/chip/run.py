#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Exits 2, with no result line, unless JAX finds a TPU with as many chips
as the cell asks for.  The last line of standard output is the result:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
with ``--trace 1`` ``breakdown``), and last ``checks``: each number the
correctness check compared, beside its limit.  See README.md.
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(_HERE)), "src"))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    main()
