"""95th percentile of the latencies of all reads in the window, healthy
and degraded together, as the client sees them."""
from chipbench.reduce import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run.ops), 95)
