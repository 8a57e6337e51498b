"""The NameNode facade: namespace + placement + detected liveness + repair.

One object ties the metadata plane together the way HDFS's NameNode
does, out of parts this repo already has:

  - :class:`~repro.namenode.Namespace` owns paths and extent maps;
  - a :class:`~repro.namenode.PlacementPolicy` (shared with the
    cluster's ``MetadataService``) decides where new blocks land;
  - datanode liveness comes from ``repro.membership`` — datanodes
    heartbeat (:meth:`heartbeat`), :meth:`tick` polls the lease-gated
    :class:`~repro.membership.ViewManager`, and a *detected* view
    change (never an omniscient ``crash()``) marks the removed node's
    blocks under-replicated;
  - the :class:`~repro.namenode.BlockReplicator` re-replicates them
    through the existing :class:`repro.control.RepairPacer` token
    bucket, copying bytes via ``StorageCluster.re_replicate``.

A file created under a directory with an erasure-coding policy
(:meth:`NameNode.set_ec_policy`) is striped as HDFS stripes it: each
block is a block group of k + m internal blocks on distinct datanodes,
its bytes dealt in cells round-robin over the k data blocks, encoded
on the cluster's batched codec (the chip's kernels on a TPU) and
rebuilt from any k internal blocks when nodes are down.

The facade also keeps per-op RPC counters (``lookups`` / ``opens`` /
``commits``) — the functional twin of the timed-plane metadata
policies (``PolicySpec(op="lookup" | "open" | "commit")``), which cost
those same RPCs in nanoseconds on a NIC handler or a host CPU — and
counts the block groups and internal blocks it wrote
(``repro.trace.namenode_registry``).  Its RPCs open ``repro.trace.wall``
spans in the ``metadata`` layer: ``nn.create``, ``nn.add_block``
(allocation, layout and the write under it), ``nn.commit`` (extent map,
length and generation stamp: HDFS's ``complete``) and ``nn.read``.
"""

from __future__ import annotations

import numpy as np

from repro.membership.detector import MembershipConfig
from repro.membership.view import View, ViewManager
from repro.trace import wall

from .namespace import Block, DirNode, ECPolicy, FileNode, Namespace
from .placement import PlacementPolicy
from .replicator import BlockReplicator

__all__ = ["NameNode"]


class NameNode:
    """Metadata server for one cluster of datanodes.

    ``cluster`` is a :class:`repro.checkpoint.StorageCluster` (or None
    for bookkeeping-only runs — e.g. placement-policy property tests);
    when present the NameNode shares the cluster's placement policy,
    routes block writes through it, and injects
    ``cluster.re_replicate`` as the replicator's copier.  ``datanodes``
    defaults to the cluster's node ids; ``cfg`` configures the failure
    detector (heartbeat interval, phi-thresholds, lease span)."""

    def __init__(self, cluster=None, placement: PlacementPolicy | None = None,
                 datanodes=None, cfg: MembershipConfig | None = None,
                 pacer=None, now: float = 0.0):
        if cluster is None and placement is None:
            raise ValueError("need a cluster or an explicit placement policy")
        self.cluster = cluster
        self.placement = placement or cluster.meta.placement
        if cluster is not None and placement is not None:
            # one ledger: the cluster's allocator must feed the same
            # policy the NameNode places with
            cluster.meta.placement = placement
        if datanodes is None:
            if cluster is None:
                raise ValueError("need datanodes when running clusterless")
            datanodes = range(cluster.num_nodes)
        self.namespace = Namespace()
        self.views = ViewManager(datanodes, cfg or MembershipConfig(),
                                 now=now)
        self.views.on_change.append(self._on_view_change)
        copier = self._copy_block if cluster is not None else None
        self.replicator = BlockReplicator(self.namespace, self.placement,
                                          copier=copier, pacer=pacer)
        self._layouts: dict[int, object] = {}   # object_id -> ObjectLayout
        # RPC ledger (the timed plane costs these same three ops)
        self.lookups = 0
        self.opens = 0
        self.commits = 0
        # erasure-coded writes: block groups, and their internal blocks
        # written and left empty
        self.block_groups = 0
        self.internal_blocks = 0
        self.empty_internal_blocks = 0

    # -- metadata RPCs -------------------------------------------------------

    def lookup(self, path: str):
        self.lookups += 1
        return self.namespace.lookup(path)

    def listdir(self, path: str) -> list[str]:
        self.lookups += 1
        return self.namespace.listdir(path)

    def mkdir(self, path: str):
        self.opens += 1
        return self.namespace.mkdir(path)

    def set_ec_policy(self, path: str, policy: ECPolicy | None) -> DirNode:
        """HDFS's ``setErasureCodingPolicy``: files created under
        directory ``path`` from now on are striped under ``policy``."""
        return self.namespace.set_ec_policy(path, policy)

    def create(self, path: str, replication: int = 3) -> FileNode:
        with wall.span("nn.create", "metadata"):
            self.opens += 1
            return self.namespace.create(path, replication)

    def add_block(self, path: str, data: bytes | np.ndarray) -> Block:
        """Append ``data`` as one block of ``path`` and commit it (one
        commit on the RPC ledger; the open happened at ``create``).

        A replicated file's block is placed via the policy and its
        replicas written through the cluster's policy engine.  An
        erasure-coded file's block is a block group: k + m distinct live
        datanodes; ``data`` dealt in the policy's cells over the k data
        blocks, with HDFS's internal lengths; all its rows encoded in one
        batched ``RSCode.encode_stripes`` call; and only its non-empty
        internal blocks written."""
        from repro.core.erasure import internal_lengths, split_cells
        from repro.core.packets import Resiliency

        with wall.span("nn.add_block", "metadata"):
            f = self.namespace.lookup(path)
            if not isinstance(f, FileNode):
                raise IsADirectoryError(path)
            if self.cluster is None:
                raise RuntimeError("clusterless NameNode cannot store bytes")
            pol, lens = f.ec_policy, None
            if pol is None:
                layout = self.cluster.write_object(
                    data, resiliency=Resiliency.REPLICATION, k=f.replication
                )
            else:
                blob = np.frombuffer(bytes(data), np.uint8) if isinstance(
                    data, (bytes, bytearray)) else np.asarray(data, np.uint8)
                if not blob.size:
                    raise ValueError("a block group holds at least one byte")
                lens = internal_lengths(blob.size, pol.k, pol.m,
                                        pol.cell_bytes)
                layout = self.cluster.write_stripes(
                    [split_cells(blob, pol.k, pol.cell_bytes)], [blob.size],
                    pol.k, pol.m, [lens])[0]
        with wall.span("nn.commit", "metadata"):
            self.commits += 1
            blk = self.namespace.commit_block(
                f, layout.size,
                [c.node for c in layout.data_coords + layout.parity_coords],
                object_id=layout.object_id, internal_lens=lens,
            )
            self._layouts[layout.object_id] = layout
            if lens is not None:
                written = sum(1 for n in lens if n)
                self.block_groups += 1
                self.internal_blocks += written
                self.empty_internal_blocks += len(lens) - written
            return blk

    def read_block(self, block: Block) -> bytes:
        """A block's bytes.  A block group with internal blocks missing
        is rebuilt from any k of them by ``RSCode.decode_stripes``, its
        empty data blocks taken as zeros; like HDFS's striped reader it
        does not re-encode to check the rebuilt bytes."""
        with wall.span("nn.read", "metadata"):
            self.lookups += 1
            return self.cluster.read_object(self._layouts[block.object_id],
                                            verify=False)

    # -- liveness (detected, never omniscient) -------------------------------

    def heartbeat(self, node: int, now: float) -> View:
        """One datanode heartbeat; a crashed node simply stops calling."""
        return self.views.record_heartbeat(node, now)

    def tick(self, now: float) -> View | None:
        """Advance detection; a newly activated view (if any) has
        already had its removals queued for re-replication."""
        return self.views.poll(now)

    def _on_view_change(self, view: View) -> None:
        dead = self.views.removed - self.replicator.dead
        if self.cluster is not None:
            # steer future placements away from *detected*-dead nodes
            # without touching the injector's omniscient ``failed`` set
            self.cluster.meta.suspected |= dead
        self.replicator.mark_dead(dead)

    def under_replicated(self) -> int:
        return self.replicator.pending()

    def re_replicate(self) -> dict:
        """Drain the under-replicated queue (paced by the injected
        :class:`~repro.control.RepairPacer`, if any)."""
        return self.replicator.run()

    def _copy_block(self, block: Block, src: int, dst: int) -> None:
        self.cluster.re_replicate(self._layouts[block.object_id], src, dst)

    # -- introspection -------------------------------------------------------

    def rpc_counts(self) -> dict:
        return {"lookups": self.lookups, "opens": self.opens,
                "commits": self.commits}
