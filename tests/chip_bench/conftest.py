"""Shared set-up of the chip benchmark's tests: the harness's package on
the path, the harness steered off the persistent compile cache, and
tiny sizes for every cell, so that a whole run takes a second on the
CPU.  A cell's tiny sizes are its own file, ``tiny/<cell>.json``:
``{"config": {...}, "traffic": {...}}``, each updating the keys of the
cell's configuration and traffic files."""

import json
import os
import sys

import pytest

BENCH_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "benchmarks", "chip"))
sys.path.insert(0, BENCH_DIR)
TINY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def tiny_sizes(cell: str) -> dict:
    """The overrides in ``tiny/<cell>.json``; a cell without that file
    is refused with the file's path."""
    path = os.path.join(TINY_DIR, cell + ".json")
    if not os.path.exists(path):
        root = os.path.dirname(os.path.dirname(BENCH_DIR))
        raise FileNotFoundError(
            f"cell {cell!r} has no tiny sizes: add "
            f"{os.path.relpath(path, root)} holding "
            f'{{"config": {{...}}, "traffic": {{...}}}}')
    with open(path) as f:
        return json.load(f)


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
            allow_cpu=True, hook=hook,
            overrides=overrides or tiny_sizes(cell))
        out = capsys.readouterr()
        return result, out.out.strip().splitlines(), out.err

    return run


@pytest.fixture
def tiny():
    """``tiny(cell)``: the cell's tiny sizes."""
    return tiny_sizes
