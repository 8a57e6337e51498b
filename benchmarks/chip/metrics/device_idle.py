"""Percent of the window in which no operation ran on the device, from
the profiler's trace (averaged over the chips used)."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_s(run.window)
    if busy is None:
        return None
    return 100 * (1 - busy / run.elapsed_s)
