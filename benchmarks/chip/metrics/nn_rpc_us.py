"""Microseconds of the NameNode's own work an RPC: the window's time in
``nn_self_share`` over the RPCs on its ledger in the window
(``nn.opens`` + ``nn.commits`` + ``nn.lookups``)."""
from chipbench.harness import reader
from chipbench.reduce import measure

RPCS = ("nn.opens", "nn.commits", "nn.lookups")


def read(run):
    share = reader("nn_self_share")(run)
    rpcs = sum(run.counters.get(name, 0) for name in RPCS)
    if share is None or not rpcs:
        return None
    return share / 100 * measure(run.window) / rpcs / 1e3
