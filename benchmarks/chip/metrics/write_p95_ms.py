"""95th percentile of the latencies of all write operations in the window."""
from chipbench.reduce import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run.ops), 95)
