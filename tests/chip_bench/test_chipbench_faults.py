"""The check judges a broken data plane not correct: the control (the
plain reference in the coding layer at RS(k, m-1) strength) and every
planted fault each cell can have, driven through a whole run on the CPU
with the harness's look for a chip skipped."""

import pytest

from chipbench import cells, faults, harness

BENCH = harness.load_bench()
KIND = {w["name"]: harness.cell_files(BENCH, w["name"])[2]["op"]
        for w in BENCH["workloads"]}
CASES = [(cell, fault) for cell, op in KIND.items()
         for fault in cells.kind(op).FAULTS]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f}" for c, f in CASES])
def test_fault_is_judged_not_correct(run_cell, cell, fault):
    undo = []
    try:
        result, out, err = run_cell(
            cell, seed=2**31 + 3,
            hook=lambda c: undo.append(faults.install(fault, c.config)))
    finally:
        for u in undo:
            u()
    assert undo, "the fault was installed"
    assert not result["correct"]
    wrong = {n: c["value"] for n, c in result["checks"].items()
             if c["value"] > c["limit"]}
    assert wrong, result["checks"]


def test_faults_are_taken_out_again(run_cell):
    """A run after a fault's undo is correct again: nothing leaks into
    the next run of the same process."""
    undo = []
    run_cell("hdfs-rs6-3-1m.stream-write", seed=1,
             hook=lambda c: undo.append(faults.install("control", c.config)))
    undo[0]()
    result, _, _ = run_cell("hdfs-rs6-3-1m.stream-write", seed=1)
    assert result["correct"]


def test_every_kind_names_its_faults_and_can_have_the_control():
    for op in cells.kind_names():
        mod = cells.kind(op)
        assert issubclass(mod.Kind, cells.Cell)
        assert "control" in mod.FAULTS and set(mod.FAULTS) <= set(faults.NAMES)
    with pytest.raises(KeyError):
        faults.install("no-such-fault", {})


def test_control_script_reports_each_seed(monkeypatch, capsys, tiny):
    import importlib.util
    import os

    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "off")
    path = os.path.join(harness.HERE, "control.py")
    spec = importlib.util.spec_from_file_location("chipbench_control", path)
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    results = control.main(
        ["--workload", "hdfs-rs6-3-1m.repair", "--fault", "altered_answer",
         "--seconds", "0.2", "--seeds", "4", "5"],
        allow_cpu=True, overrides=tiny("hdfs-rs6-3-1m.repair"))
    assert [r["correct"] for r in results] == [False, False]
    assert "2 of 2 runs judged not correct" in capsys.readouterr().err
