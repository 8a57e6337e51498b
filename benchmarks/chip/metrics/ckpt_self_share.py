"""Percent of each save spent in CheckpointManager.save outside calls into
StorageCluster: the device-to-host snapshot, tobytes, the MAC."""
from chipbench.reduce import self_share


def read(run):
    return self_share(run.spans, "save", "cluster")
