"""Shared set-up of the chip benchmark's tests: the harness's package on
the path, the harness steered off the persistent compile cache, and
tiny sizes for every cell so that a whole run takes a second on the
CPU."""

import os
import sys

import pytest

BENCH_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "benchmarks", "chip"))
sys.path.insert(0, BENCH_DIR)

KiB = 1024
#: a tiny checkpoint state: two groups, a repeated leaf that takes two
#: stripe objects on each of two chips, and one that takes a short one
TINY_STATE = {"groups": ["params", "opt_state/mu"], "dtype": "float32",
              "fsdp_chips": 2,
              "leaves": [{"path": "w/{i}", "shape": [96, 64], "repeat": 2},
                         {"path": "n", "shape": [1002]}]}
#: cell -> overrides of its configuration and traffic files
TINY = {
    "hdfs-rs6-3-1m.stream-write": {"traffic": {
        "object_bytes": 24 * KiB, "pool_objects": 4,
        "cluster_bytes": 12 * 36 * KiB, "readback_objects": 3}},
    "ckpt-rs6-3.save": {
        "config": {"stripe_bytes": 6 * KiB, "state": TINY_STATE},
        "traffic": {"cluster_bytes": 300 * KiB, "checked_objects": 4,
                    "readback_objects": 2}},
    "hdfs-rs6-3-1m.degraded-read": {
        "config": {"dataset_objects": 24},
        "traffic": {"object_bytes": 24 * KiB, "kept_share": 0.5,
                    "kept_results": 8}},
    "hdfs-rs6-3-1m.repair": {
        "config": {"dataset_objects": 24},
        "traffic": {"object_bytes": 24 * KiB, "readback_objects": 3}},
}


@pytest.fixture
def run_cell(monkeypatch, capsys):
    """``run_cell(cell, seed, trace=0, hook=None, seconds=0.2,
    overrides=None)``: one tiny run on the CPU (``overrides`` in place of
    the cell's tiny sizes); returns (result, stdout lines, stderr text)."""
    from chipbench import harness

    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")

    def run(cell, seed, trace=0, hook=None, seconds=0.2, overrides=None):
        result = harness.main(
            ["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", str(trace)],
            allow_cpu=True, hook=hook, overrides=overrides or TINY[cell])
        out = capsys.readouterr()
        return result, out.out.strip().splitlines(), out.err

    return run


@pytest.fixture
def tiny():
    return TINY
