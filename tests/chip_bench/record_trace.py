#!/usr/bin/env python3
"""Record the small TPU trace that ``test_chipbench_reduce.py`` reduces.

Profiles three RS(6,3) encodes of one 6 MiB stripe through
``RSCode.encode_stripes`` (one warm-up first, so nothing compiles in the
trace) inside the benchmark's window annotation, copies the profiler's
``.xplane.pb`` to the given path, and prints what the test checks, read
straight from the planes: the annotation's start and the device
durations of the ``XLA Modules`` events, which the test's constants
are copied from when the trace is recorded anew.  Needs a TPU.

    python3 tests/chip_bench/record_trace.py tests/chip_bench/data/encode_s1.xplane.pb
"""

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(out: str) -> None:
    import jax
    import numpy as np

    from chipbench.cache import setup_compile_cache
    from chipbench.reduce import WINDOW_ANNOTATION
    from repro.core.erasure import RSCode

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    setup_compile_cache()
    data = np.random.default_rng(0).integers(0, 256, (1, 6, 1 << 20),
                                             dtype=np.uint8)
    code = RSCode(6, 3)
    code.encode_stripes(data)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            for _ in range(3):
                code.encode_stripes(data)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True)
        shutil.copyfile(path, out)
    planes = jax.profiler.ProfileData.from_file(out).planes
    for plane in planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_ANNOTATION or line.name == "XLA Modules":
                    print(plane.name, line.name, e.name, int(e.start_ns),
                          int(e.duration_ns))
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
