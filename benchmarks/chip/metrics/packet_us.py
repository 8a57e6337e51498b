"""Microseconds of the packet plane a packet: the window's union of the
program's ``dfs.write`` and ``dfs.read`` spans (framing, delivery through
the router, the nodes' handlers with their capability checks, acks and
responses, read assembly) over the packets delivered to nodes and to
clients.  One reader for every ``packet_us.<end-to-end metric>`` split."""
from chipbench.reduce import measure, program_union

SPANS = ("dfs.write", "dfs.read")


def read(run):
    if not run.program_spans:
        return None
    packets = (run.counters.get("packets.to_nodes", 0)
               + run.counters.get("packets.to_clients", 0))
    inside = measure(program_union(run.program_spans, SPANS, run.window))
    if not packets or not inside:
        return None
    return inside / packets / 1e3
