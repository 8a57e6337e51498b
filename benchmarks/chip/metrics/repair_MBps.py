"""Bytes of lost shards rebuilt and written back, per second of window."""
from chipbench.reduce import rate_MBps


def read(run):
    return rate_MBps(run.ops, run.elapsed_s)
