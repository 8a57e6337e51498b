"""Percent of the window the host spent inside the program's
``rs.encode`` / ``rs.decode`` spans while no operation ran on the
device: the coding layer's host half (padding, dispatch, copies, the
wait for the device's result less the device's own time), from the
program's spans and the profiler's trace, averaged over the chips
used."""
from chipbench.reduce import gaps, intersect, measure, program_union

SPANS = ("rs.encode", "rs.decode")


def read(run):
    if run.trace is None or not run.program_spans:
        return None
    coding = program_union(run.program_spans, SPANS, run.window)
    busy = run.trace.busy(run.window)
    if not coding or not busy:
        return None
    idle = sum(measure(intersect(coding, gaps(b, run.window)))
               for b in busy.values()) / len(busy)
    return 100 * idle / measure(run.window)
