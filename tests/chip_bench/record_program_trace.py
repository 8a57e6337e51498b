#!/usr/bin/env python3
"""Record the TPU trace that ``test_chipbench_program.py`` reduces: the
program's own spans as annotations beside the device's events.

With the data plane's wall-clock tracer installed (``repro.trace.wall``),
profiles, inside the benchmark's window annotation, two writes of one
6 MiB RS(6,3) object through ``StorageCluster.write_object_bulk`` and
one read of it with a data node down (decode and the verify encode),
on a fresh cluster, after the same work once so that nothing compiles
in the trace.
Copies the profiler's ``.xplane.pb`` to the given path and prints the
program spans' count by name and the window annotation.  Needs a TPU.

    python3 tests/chip_bench/record_program_trace.py \
        tests/chip_bench/program_trace/program_s1.xplane.pb
"""

import collections
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
    from repro.checkpoint.storage import StorageCluster
    from repro.trace import Tracer, wall

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_program_trace: needs a TPU")
    setup_compile_cache()
    blob = np.random.default_rng(0).integers(0, 256, 6 << 20, dtype=np.uint8)

    def work():
        cluster = StorageCluster(12, node_capacity=8 << 20)
        layouts = [cluster.write_object_bulk([blob], k=6, m=3)[0]
                   for _ in range(2)]
        cluster.fail_node(layouts[0].data_coords[0].node)
        assert cluster.read_objects(layouts[:1])[0] == blob.tobytes()

    work()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tracer = Tracer.wall()
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        wall.install(tracer)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                work()
        finally:
            wall.uninstall()
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True)
        shutil.copyfile(path, out)
    print("program spans:", dict(collections.Counter(
        s.name for s in tracer.spans)))
    for plane in jax.profiler.ProfileData.from_file(out).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_ANNOTATION:
                    print(plane.name, line.name, e.name, int(e.start_ns),
                          int(e.duration_ns))
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
