"""Checkpoint storage cluster: DFS storage nodes + metadata service.

This instantiates the paper's architecture for the training framework:
a set of storage nodes whose "NICs" run the policy engine
(``repro.core.handlers``), a metadata service that owns the object
namespace and issues capabilities, and a client used by the checkpoint
manager.  Storage is byte-addressable memory per node (optionally spilled
to disk files), the paper's NVMM assumption.

The metadata service implements the control plane the paper leaves
abstract: object -> (layout, policy) mapping, extent allocation, and
capability issuance (section II: clients query metadata, then talk to
storage nodes directly).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import secrets
import threading
import time

import numpy as np

from repro.core.auth import CapabilityAuthority, Rights
from repro.core.handlers import DFSClient, DFSNode, Router
from repro.core.packets import OpType, ReplicaCoord, ReplStrategy, Resiliency
from repro.namenode.placement import PlacementPolicy, RoundRobinPlacement
from repro.policy.functional import write_plan
from repro.trace import wall


@dataclasses.dataclass
class ObjectLayout:
    """Where one object lives: data/parity extents on storage nodes."""

    object_id: int
    size: int
    resiliency: Resiliency
    strategy: ReplStrategy
    data_coords: list[ReplicaCoord]
    parity_coords: list[ReplicaCoord]
    ec_k: int = 0
    ec_m: int = 0
    chunk_len: int = 0  # per-node chunk length (EC) or full size (repl)
    #: EC: rows of k data cells of ``chunk_len`` bytes; shard ``i`` is its
    #: cell of every row, in row order, cut to ``slot_lens[i]``
    stripes: int = 1
    #: EC: bytes each of the k + m shards stores.  0 is an empty internal
    #: block (a short file's unused data cells): it keeps its placed node,
    #: takes no extent (``addr`` -1) and no write, and reads as zeros
    slot_lens: tuple[int, ...] = ()
    #: set by repair when the object exceeded its loss tolerance: reads
    #: raise and the audit ledger pins the bytes as lost — re-provisioned
    #: nodes must not resurrect zeroed shards as "readable"
    lost: bool = False


class MetadataService:
    """Control plane: namespace, extent allocation, capabilities."""

    def __init__(self, num_nodes: int, node_capacity: int,
                 key: bytes | None = None,
                 placement: PlacementPolicy | None = None):
        self.authority = CapabilityAuthority(key or secrets.token_bytes(16))
        self.num_nodes = num_nodes
        self.node_capacity = node_capacity
        self._alloc = [0] * num_nodes  # bump allocator per node
        self._objects: dict[int, ObjectLayout] = {}
        self._next_oid = 1
        #: pluggable placement (repro.namenode.placement) — replaces the
        #: old private ``_rr`` cursor, whose scan-count advance skewed
        #: load onto the node after a failed one
        self.placement = placement or RoundRobinPlacement(num_nodes)
        #: nodes excluded from new placements (StorageCluster aliases its
        #: ``failed`` set here, so crashes steer future writes away)
        self.unavailable: set[int] = set()
        #: *detected*-dead exclusions (the NameNode's view changes land
        #: here): kept apart from ``unavailable`` so detection never
        #: mutates the fault injector's omniscient ``failed`` set
        self.suspected: set[int] = set()

    def _place(self, n: int) -> list[int]:
        return self.placement.place(n, self.unavailable | self.suspected)

    def _extent(self, node: int, size: int) -> int:
        addr = self._alloc[node]
        if addr + size > self.node_capacity:
            raise RuntimeError(f"storage node {node} full")
        self._alloc[node] = addr + size
        self.placement.record(node, size)
        return addr

    def create_object(
        self,
        size: int,
        resiliency: Resiliency,
        k: int,
        m: int = 0,
        strategy: ReplStrategy = ReplStrategy.RING,
        stripes: int = 1,
        chunk_len: int | None = None,
        slot_lens: tuple[int, ...] | None = None,
    ) -> ObjectLayout:
        """Place and allocate one object.  An EC object is by default one
        row of k equal cells (:func:`repro.core.erasure.split_stripe`);
        ``stripes``, ``chunk_len`` and ``slot_lens`` describe any other
        row layout (HDFS's cell striping) and the bytes each shard keeps."""
        oid = self._next_oid
        self._next_oid += 1
        if resiliency == Resiliency.ERASURE_CODING:
            if chunk_len is None:
                chunk_len = -(-size // k)
                chunk_len = -(-chunk_len // 32) * 32  # stripe alignment
            if slot_lens is None:
                slot_lens = (chunk_len,) * (k + m)
            nodes = self._place(k + m)
            coords = [ReplicaCoord(n, self._extent(n, ln) if ln else -1)
                      for n, ln in zip(nodes, slot_lens)]
            layout = ObjectLayout(oid, size, resiliency, strategy,
                                  coords[:k], coords[k:], ec_k=k, ec_m=m,
                                  chunk_len=chunk_len, stripes=stripes,
                                  slot_lens=tuple(slot_lens))
        elif resiliency == Resiliency.REPLICATION:
            nodes = self._place(k)
            data = [ReplicaCoord(n, self._extent(n, size)) for n in nodes]
            layout = ObjectLayout(oid, size, resiliency, strategy, data, [],
                                  chunk_len=size)
        else:
            node = self._place(1)
            data = [ReplicaCoord(node[0], self._extent(node[0], size))]
            layout = ObjectLayout(oid, size, resiliency, strategy, data, [],
                                  chunk_len=size)
        self._objects[oid] = layout
        return layout

    def lookup(self, oid: int) -> ObjectLayout:
        return self._objects[oid]

    def issue_capability(
        self, client_id: int, rights: int = Rights.WRITE | Rights.READ,
        ttl_s: int = 3600,
    ):
        # Extent-wide capability: per-object capabilities are issued by
        # narrowing offset/length (see CheckpointManager).
        return self.authority.issue(
            client_id=client_id,
            object_id=0,
            offset=0,
            length=self.node_capacity,
            rights=rights,
            expiry=int(time.time()) + ttl_s,
        )


def _shard_of(cell_rows: np.ndarray, length: int) -> np.ndarray:
    """A shard's bytes from its (S, L) cells of every row: the rows in
    order, cut to ``length``."""
    if cell_rows.shape[0] == 1:
        return cell_rows[0, :length]
    return cell_rows.reshape(-1)[:length]


def _io_locked(fn):
    """Serialize a packet-plane method on the cluster's I/O lock."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._io_lock:
            return fn(self, *args, **kwargs)

    return wrapper


class StorageCluster:
    """N policy-enforcing storage nodes + a metadata service + a client."""

    def __init__(
        self,
        num_nodes: int,
        node_capacity: int = 1 << 26,
        client_id: int = 1,
        spill_dir: str | None = None,
        placement: PlacementPolicy | None = None,
    ):
        self.router = Router()
        self.meta = MetadataService(num_nodes, node_capacity,
                                    placement=placement)
        self.nodes = [
            DFSNode(i, self.router, self.meta.authority,
                    storage_size=node_capacity)
            for i in range(num_nodes)
        ]
        self.client = DFSClient(client_id, self.router)
        self.client_id = client_id
        self.capability = self.meta.issue_capability(client_id)
        self.spill_dir = spill_dir
        self.num_nodes = num_nodes
        self.node_capacity = node_capacity
        self.failed: set[int] = set()
        # the metadata service places new extents on live nodes only
        self.meta.unavailable = self.failed
        # serializes packet-plane operations (reads/writes/repair): the
        # Router is synchronous and not thread-safe, and background
        # repair / async checkpoint saves run on their own threads
        self._io_lock = threading.RLock()
        #: bounded retry budget for shard reads under packet loss: a
        #: lossy link (see :meth:`set_failures`) drops read requests /
        #: responses, and each failed attempt is retried up to this many
        #: times before the shard is treated as missing (degraded-read
        #: reconstruction takes over).  Counted in the audit ledger.
        self.max_read_retries = 3
        self.read_retries = 0      # extra attempts that were needed
        self.read_timeouts = 0     # shards given up on after the budget

    # -- data plane -----------------------------------------------------------

    @_io_locked
    def write_object(
        self,
        data: bytes | np.ndarray,
        resiliency: Resiliency = Resiliency.ERASURE_CODING,
        k: int = 4,
        m: int = 2,
        strategy: ReplStrategy = ReplStrategy.RING,
        spec=None,
    ) -> ObjectLayout:
        """Write one object.  ``spec`` (a :class:`repro.policy.PolicySpec`)
        overrides the positional policy knobs; an ``RS(engine='client')``
        spec routes through the batched host encode
        (:meth:`write_object_bulk`)."""
        if spec is not None:
            plan = write_plan(spec)
            if plan.kind == "ec-client":
                return self.write_object_bulk([data], k=plan.k, m=plan.m)[0]
            if plan.kind == "flat":
                raise NotImplementedError(
                    "Flat replication has no object layout; use a Tree spec"
                )
            resiliency, strategy = plan.resiliency, plan.strategy
            k, m = plan.k, plan.m
        blob = np.frombuffer(bytes(data), np.uint8) if isinstance(
            data, (bytes, bytearray)) else np.asarray(data, np.uint8).ravel()
        layout = self.meta.create_object(
            int(blob.size), resiliency, k, m, strategy
        )
        try:
            self._write_object_shards(layout, blob, resiliency, m, strategy)
        except IOError:
            # a placed node crashed between allocation and the write: drop
            # the dead layout, re-place on live nodes, retry once
            del self.meta._objects[layout.object_id]
            layout = self.meta.create_object(
                int(blob.size), resiliency, k, m, strategy
            )
            self._write_object_shards(layout, blob, resiliency, m, strategy)
        return layout

    def _write_object_shards(
        self,
        layout: ObjectLayout,
        blob: np.ndarray,
        resiliency: Resiliency,
        m: int,
        strategy: ReplStrategy,
    ) -> None:
        before = len(self.client.acks())
        if resiliency == Resiliency.ERASURE_CODING:
            self.client.write(
                self.capability, blob, list(layout.data_coords),
                resiliency=resiliency, ec_m=m,
                parity_targets=list(layout.parity_coords),
            )
            expect = layout.ec_k + layout.ec_m
        else:
            self.client.write(
                self.capability, blob, list(layout.data_coords),
                resiliency=resiliency, strategy=strategy,
            )
            expect = 1
        self._check_acks(layout, before, expect)

    def _check_acks(self, layout: ObjectLayout, before: int, expect: int) -> None:
        acks = self.client.acks()[before:]
        good = [a for a in acks if a.ctrl == OpType.WRITE_ACK]
        if len(good) < expect:
            raise IOError(
                f"object {layout.object_id}: {len(good)}/{expect} acks "
                f"(NACK or loss)"
            )

    @_io_locked
    def write_object_bulk(
        self,
        blobs: list[bytes | np.ndarray],
        k: int = 4,
        m: int = 2,
        backend: str | None = None,
    ) -> list[ObjectLayout]:
        """Batched client-side EC — the ``RS(engine='client')`` plan: each
        blob is one row of k equal cells
        (:func:`~repro.core.erasure.split_stripe`), written by
        :meth:`write_stripes`."""
        from repro.core.erasure import split_stripe

        with wall.span("cluster.write", "entry"):
            arrs = [
                np.frombuffer(bytes(b), np.uint8)
                if isinstance(b, (bytes, bytearray))
                else np.asarray(b, np.uint8).ravel()
                for b in blobs
            ]
            return self._write_stripes([split_stripe(a, k)[None] for a in arrs],
                                       [a.size for a in arrs], k, m, None,
                                       backend)

    @_io_locked
    def write_stripes(
        self,
        rows: list[np.ndarray],
        sizes: list[int],
        k: int,
        m: int,
        slot_lens: list[tuple[int, ...]] | None = None,
        backend: str | None = None,
    ) -> list[ObjectLayout]:
        """Encode and write objects already split into rows of cells.

        Object ``i`` holds ``sizes[i]`` bytes as ``rows[i]``, (S, k, L)
        data cells; its shard ``j`` is its cell of every row, cut to
        ``slot_lens[i][j]`` bytes (default: whole), and an empty shard
        takes no extent and no write.  All rows of one width are encoded
        in *one* ``RSCode.encode_stripes`` call (backend="jax" is a
        single fused kernel dispatch per piece; None takes the platform's
        data-plane default, the kernels on a TPU), then every non-empty
        data/parity shard is written as an authenticated plain write
        through the policy engine."""
        with wall.span("cluster.write", "entry"):
            return self._write_stripes(rows, sizes, k, m, slot_lens, backend)

    def _write_stripes(self, rows, sizes, k: int, m: int, slot_lens,
                       backend: str | None) -> list[ObjectLayout]:
        from repro.core.erasure import RSCode

        lens = slot_lens or [None] * len(rows)

        def create(i: int) -> ObjectLayout:
            return self.meta.create_object(
                int(sizes[i]), Resiliency.ERASURE_CODING, k, m,
                ReplStrategy.RING, stripes=rows[i].shape[0],
                chunk_len=rows[i].shape[2], slot_lens=lens[i])

        layouts = [create(i) for i in range(len(rows))]
        # Group rows by width -> one batched encode each.
        groups: dict[int, list[int]] = {}
        for i, r in enumerate(rows):
            groups.setdefault(r.shape[2], []).append(i)
        code = RSCode(k, m)
        parities: dict[int, np.ndarray] = {}
        for length, idxs in groups.items():
            if length == 0:
                for i in idxs:
                    parities[i] = np.zeros((rows[i].shape[0], m, 0), np.uint8)
                continue
            batch = np.concatenate([rows[i] for i in idxs])    # (S, k, L)
            par = code.encode_stripes(batch, backend=backend)  # (S, m, L)
            lo = 0
            for i in idxs:
                parities[i] = par[lo:lo + rows[i].shape[0]]
                lo += rows[i].shape[0]
        for i, lay in enumerate(layouts):
            try:
                self._write_bulk_shards(lay, rows[i], parities[i])
            except IOError:
                # mid-batch crash of a placed node: re-place this object on
                # live nodes (same rows -> same shard lengths), retry once
                del self.meta._objects[lay.object_id]
                lay = layouts[i] = create(i)
                self._write_bulk_shards(lay, rows[i], parities[i])
        return layouts

    def _write_bulk_shards(
        self, lay: ObjectLayout, data: np.ndarray, parity: np.ndarray
    ) -> None:
        before = len(self.client.acks())
        cells = [data[:, j] for j in range(lay.ec_k)] + \
            [parity[:, i] for i in range(lay.ec_m)]
        coords = self._layout_coords(lay)
        written = 0
        for cell_rows, coord, length in zip(cells, coords, lay.slot_lens):
            if length:
                self.client.write(self.capability,
                                  _shard_of(cell_rows, length), [coord])
                written += 1
        self._check_acks(lay, before, written)

    def set_failures(self, failures) -> None:
        """Attach a :class:`repro.policy.FailureModel` to the functional
        plane: crashed nodes are failed at the router (blackholed until
        repaired), lossy nodes drop packets towards them with the model's
        seeded probabilities.  Loss applies to *all* traffic towards the
        node; reads carry their own bounded retry budget
        (``max_read_retries``), writes surface missing acks as
        :class:`IOError` at the caller."""
        for node in failures.crashed:
            self.fail_node(node)
        self.router.set_loss(failures.loss_map, failures.seed)

    def _read_shard(self, coord: ReplicaCoord, length: int) -> np.ndarray | None:
        """One shard through the authenticated packet read path; ``None``
        when the node is failed/unreachable (the read is blackholed) or
        still unreadable after the bounded retry budget (a lossy link
        dropped every attempt — the functional-plane "timeout").

        Retries are deliberately *bounded*: an endlessly-retrying client
        would hide a dead node as latency; after ``max_read_retries``
        extra attempts the shard is reported missing and the caller's
        degraded-read path reconstructs instead."""
        if coord.node in self.failed:
            return None
        for attempt in range(1 + self.max_read_retries):
            if attempt > 0:
                self.read_retries += 1
            try:
                return self.client.read(self.capability, coord, length)
            except IOError:
                continue
        self.read_timeouts += 1
        return None

    def _read_slot(self, layout: ObjectLayout, idx: int) -> np.ndarray | None:
        """EC shard ``idx`` of ``layout`` as its (S, L) cells of every row
        (zero past the shard's length), or ``None`` when it cannot be
        read.  An empty internal block is known zeros and is not read."""
        rows, width = layout.stripes, layout.chunk_len
        length = layout.slot_lens[idx]
        if not length:
            return np.zeros((rows, width), np.uint8)
        got = self._read_shard(self._layout_coords(layout)[idx], length)
        if got is None or length == rows * width:
            return None if got is None else got.reshape(rows, width)
        out = np.zeros(rows * width, np.uint8)
        out[:length] = got
        return out.reshape(rows, width)

    def read_object(self, layout: ObjectLayout, verify: bool = True) -> bytes:
        """Read one object (degraded-mode capable); see
        :meth:`read_objects`."""
        return self.read_objects([layout], verify=verify)[0]

    @_io_locked
    def read_objects(
        self,
        layouts: list[ObjectLayout],
        verify: bool = True,
        backend: str | None = None,
    ) -> list[bytes]:
        """Batched degraded-capable read through the packet plane.

        Every surviving shard is fetched with an authenticated
        ``DFSClient.read`` (failed nodes blackhole, so missing shards are
        *observed*, not assumed).  EC objects with missing shards are
        reconstructed by ``RSCode.decode_stripes`` — all stripes sharing
        (geometry, chunk length, erasure pattern) go through ONE batched
        decode call (the common whole-node-failure case).  With
        ``verify`` (default), recovered stripes are re-encoded and
        checked bit-exact against every surviving parity shard before
        the bytes are returned.  Replicated objects fail over to the
        first surviving replica.
        """
        with wall.span("cluster.read", "entry"):
            return self._read_objects(layouts, verify, backend)

    def _read_objects(self, layouts: list[ObjectLayout], verify: bool,
                      backend: str | None) -> list[bytes]:
        from repro.core.erasure import RSCode

        out: list[bytes | None] = [None] * len(layouts)
        # (k, m, rows, chunk_len, missing-pattern) -> [(pos, shards)]
        groups: dict[tuple, list[tuple[int, list]]] = {}
        for pos, layout in enumerate(layouts):
            if layout.lost:
                raise IOError(
                    f"object {layout.object_id}: lost (exceeded its loss "
                    f"tolerance; repair could not reconstruct it)"
                )
            if layout.resiliency == Resiliency.ERASURE_CODING:
                k = layout.ec_k
                data_shards = [self._read_slot(layout, j) for j in range(k)]
                if all(s is not None for s in data_shards):
                    # healthy fast path: the data reads, no parity
                    # traffic, no decode
                    out[pos] = np.stack(data_shards, axis=1).reshape(-1)[
                        : layout.size].tobytes()
                    continue
                # degraded: fetch parity lazily, group by erasure pattern
                shards = data_shards + [self._read_slot(layout, k + i)
                                        for i in range(layout.ec_m)]
                pattern = tuple(i for i, s in enumerate(shards) if s is None)
                key = (k, layout.ec_m, layout.stripes, layout.chunk_len,
                       pattern)
                groups.setdefault(key, []).append((pos, shards))
            elif layout.resiliency == Resiliency.REPLICATION:
                for coord in layout.data_coords:
                    got = self._read_shard(coord, layout.size)
                    if got is not None:
                        out[pos] = got.tobytes()
                        break
                else:
                    raise IOError(
                        f"object {layout.object_id}: all replicas failed")
            else:
                got = self._read_shard(layout.data_coords[0], layout.size)
                if got is None:
                    raise IOError(f"object {layout.object_id}: node failed")
                out[pos] = got.tobytes()
        for (k, m, rows, _, pattern), members in groups.items():
            code = RSCode(k, m)
            # one batched decode per (geometry, chunk, erasure pattern)
            try:
                batched, datam = self._decode_shard_group(
                    code, [shards for _, shards in members], pattern, backend)
            except ValueError as exc:
                # normalize to the method's failure contract (IOError),
                # like every other unreadable-object path
                oids = [layouts[pos].object_id for pos, _ in members]
                raise IOError(f"objects {oids}: {exc}") from exc
            if verify and pattern:
                # recovered stripes must re-encode bit-exact to every
                # surviving parity shard (the encode layout is the truth)
                par = code.encode_stripes(datam, backend=backend)
                for pi in range(m):
                    slot = k + pi
                    if slot in pattern:
                        continue
                    if not np.array_equal(par[:, pi, :], batched[slot]):
                        oids = [layouts[pos].object_id for pos, _ in members]
                        raise IOError(
                            f"reconstruction mismatch vs parity {pi} for "
                            f"objects {oids} (corrupt shard?)"
                        )
            for s, (pos, _) in enumerate(members):
                layout = layouts[pos]
                out[pos] = datam[s * rows:(s + 1) * rows].reshape(-1)[
                    : layout.size].tobytes()
        return out  # type: ignore[return-value]

    # -- failure injection / recovery ------------------------------------------

    def fail_node(self, node_id: int) -> None:
        """Crash a node: its packets are blackholed at the router and
        its shards become unreadable until repaired."""
        self.failed.add(node_id)
        self.router.fail(node_id)

    def heal_node(self, node_id: int) -> None:
        """Re-provision a node in place and rebuild every shard it held
        (thin wrapper over :meth:`repair_node`)."""
        self.repair_node(node_id)

    def repair_node(
        self,
        node_id: int,
        replacement: int | None = None,
        background: bool = False,
        pacer=None,
    ) -> dict | None:
        """Rebuild every shard ``node_id`` held.

        ``replacement=None`` re-provisions the node in place (storage
        wiped, router healed); otherwise new extents are allocated on the
        ``replacement`` node and the object layouts are repointed.  Lost
        EC shards are reconstructed through batched
        ``RSCode.decode_stripes`` / re-encoded with ``encode_stripes``
        (one call per (geometry, chunk, erasure-pattern) group) and
        written back as authenticated plain writes through the policy
        engine.  ``background=True`` runs the rebuild on a repair thread
        (:meth:`repair_wait` joins it); stats land in ``repair_stats``.

        ``pacer`` (a :class:`repro.control.RepairPacer`) throttles the
        rebuild: every rebuilt shard's bytes go through the token
        bucket, so background repair competes with foreground I/O at a
        configured rate instead of flat out — the same governor the
        timed workload engine paces its repair loads with.  The served
        wait lands in ``stats["paced_wait_s"]``.
        """
        # validate on the caller thread so bad arguments raise here, not
        # silently on the repair daemon
        if (replacement is not None and replacement != node_id
                and replacement in self.failed):
            raise ValueError(f"replacement node {replacement} is failed")
        if background:
            self.repair_stats = None
            self._repair_error: BaseException | None = None

            def run() -> None:
                try:
                    self._repair(node_id, replacement, pacer)
                except BaseException as exc:  # surfaced by repair_wait
                    self._repair_error = exc

            self._repair_thread = threading.Thread(target=run, daemon=True)
            self._repair_thread.start()
            return None
        return self._repair(node_id, replacement, pacer)

    def repair_wait(self) -> dict | None:
        """Join a background repair; re-raises its exception (a repair
        that died must not read as a success) and returns its stats."""
        t = getattr(self, "_repair_thread", None)
        if t is not None and t.is_alive():
            t.join()
        err = getattr(self, "_repair_error", None)
        if err is not None:
            self._repair_error = None
            raise err
        return getattr(self, "repair_stats", None)

    def _layout_coords(self, layout: ObjectLayout) -> list[ReplicaCoord]:
        return list(layout.data_coords) + list(layout.parity_coords)

    def _set_coord(self, layout: ObjectLayout, idx: int,
                   coord: ReplicaCoord) -> None:
        if idx < len(layout.data_coords):
            layout.data_coords[idx] = coord
        else:
            layout.parity_coords[idx - len(layout.data_coords)] = coord

    @staticmethod
    def _decode_shard_group(code, shard_lists, pattern, backend=None):
        """Stack each slot's per-member (rows, L) shards into an (S, L)
        batch and reconstruct the whole (geometry, chunk, erasure-pattern)
        group in ONE ``decode_stripes`` call.  Returns (batched_slots,
        (S, k, L)), member by member and row by row."""
        batched = [
            None if i in pattern
            else np.concatenate([shards[i] for shards in shard_lists])
            for i in range(code.n)
        ]
        return batched, code.decode_stripes(batched, backend=backend)

    def _repair(self, node_id: int, replacement: int | None,
                pacer=None) -> dict:
        """Collect + reconstruct under the I/O lock, then write back one
        shard at a time — with any pacer wait served *outside* the lock,
        so a throttled background rebuild interleaves with foreground
        I/O instead of blocking it for the whole paced duration.

        During the write-back window the target stays in ``failed``:
        foreground reads treat its shards as missing (degraded
        reconstruction returns correct bytes) and placement avoids it —
        only the final lock acquisition marks it live again."""
        with wall.span("cluster.repair", "entry"):
            in_place = replacement is None or replacement == node_id
            if not in_place and replacement in self.failed:
                raise ValueError(f"replacement node {replacement} is failed")
            with self._io_lock:
                stats, tasks = self._repair_collect(node_id, replacement,
                                                    in_place)
            touched: set[int] = set()
            for layout, idx, shard in tasks:
                if pacer is not None:
                    stats["paced_wait_s"] += pacer.throttle(int(shard.size))
                with self._io_lock:
                    self._write_rebuilt(layout, idx, shard, node_id,
                                        replacement, stats)
                touched.add(id(layout))
            with self._io_lock:
                if in_place:
                    # every shard is back: the node may serve reads again
                    self.failed.discard(node_id)
                stats["objects"] = len(touched)
                self.repair_stats = stats
            return stats

    def _repair_collect(
        self, node_id: int, replacement: int | None, in_place: bool
    ) -> tuple[dict, list]:
        """Phases 1+2 under the caller's lock: stage every lost shard,
        reconstruct the EC groups batched, re-provision the target.
        Returns (stats, [(layout, slot, rebuilt shard), ...])."""
        from repro.core.erasure import RSCode

        stats = {"objects": 0, "shards": 0, "bytes": 0, "unrecoverable": 0,
                 "paced_wait_s": 0.0}
        # Phase 1 — collect (node_id still failed): every (layout, slot)
        # the dead node held, EC slots grouped by (k, m, chunk, erasure
        # pattern) for batched reconstruction, replication sources staged.
        # Anything unrecoverable is decided NOW, before the node comes
        # back: an in-place re-provision must not resurrect zeroed shards
        # as "readable", so those layouts are pinned lost.
        ec_groups: dict[tuple, list[tuple[ObjectLayout, int, list]]] = {}
        repl_tasks: list[tuple[ObjectLayout, int, np.ndarray]] = []
        for layout in self.meta._objects.values():
            coords = self._layout_coords(layout)
            for idx, coord in enumerate(coords):
                if coord.node != node_id or layout.lost:
                    continue
                if layout.resiliency == Resiliency.ERASURE_CODING:
                    if not layout.slot_lens[idx]:
                        continue        # an empty internal block: nothing lost
                    shards = [
                        None if c.node == node_id and layout.slot_lens[i]
                        else self._read_slot(layout, i)
                        for i, c in enumerate(coords)
                    ]
                    if sum(s is not None for s in shards) < layout.ec_k:
                        self._mark_unrecoverable(layout, in_place, stats)
                        continue
                    pattern = tuple(
                        i for i, s in enumerate(shards) if s is None)
                    key = (layout.ec_k, layout.ec_m, layout.stripes,
                           layout.chunk_len, pattern)
                    ec_groups.setdefault(key, []).append(
                        (layout, idx, shards))
                elif layout.resiliency == Resiliency.REPLICATION:
                    src = next(
                        (c for c in coords
                         if c.node != node_id and c.node not in self.failed),
                        None,
                    )
                    data = (self._read_shard(src, layout.size)
                            if src is not None else None)
                    if data is None:
                        self._mark_unrecoverable(layout, in_place, stats)
                        continue
                    repl_tasks.append((layout, idx, data))
                else:
                    # the only copy is gone
                    self._mark_unrecoverable(layout, in_place, stats)
        # Phase 2 — re-provision the target: storage wiped and router
        # healed so rebuilt writes land, but the node stays in ``failed``
        # (reads keep reconstructing around it, placement avoids it)
        # until the caller finishes the write-back.
        if in_place:
            self.nodes[node_id].storage.mem[:] = 0
            self.router.heal(node_id)
        # Reconstruct the EC groups batched; the caller writes back.
        tasks: list = list(repl_tasks)
        for (k, m, rows, _, pattern), members in ec_groups.items():
            code = RSCode(k, m)
            _, datam = self._decode_shard_group(
                code, [shards for _, _, shards in members], pattern)
            parm = None
            if any(idx >= k for _, idx, _ in members):
                parm = code.encode_stripes(datam)
            for s, (layout, idx, _) in enumerate(members):
                cells = (datam[s * rows:(s + 1) * rows, idx] if idx < k
                         else parm[s * rows:(s + 1) * rows, idx - k])
                tasks.append((layout, idx,
                              _shard_of(cells, layout.slot_lens[idx])))
        return stats, tasks

    @staticmethod
    def _mark_unrecoverable(layout: ObjectLayout, in_place: bool,
                            stats: dict) -> None:
        stats["unrecoverable"] += 1
        if in_place:
            # the zeroed re-provisioned shard must never masquerade as
            # data: the object is explicitly lost (reads raise, audit
            # counts the bytes as lost)
            layout.lost = True

    def _write_rebuilt(
        self,
        layout: ObjectLayout,
        idx: int,
        shard: np.ndarray,
        node_id: int,
        replacement: int | None,
        stats: dict,
    ) -> None:
        """Write one rebuilt shard via an authenticated plain write and
        repoint the layout when repairing onto a replacement node."""
        coord = self._layout_coords(layout)[idx]
        if replacement is not None and replacement != node_id:
            addr = self.meta._extent(replacement, int(shard.size))
            coord = ReplicaCoord(replacement, addr)
            self._set_coord(layout, idx, coord)
        self.client.write(self.capability, shard, [coord])
        stats["shards"] += 1
        stats["bytes"] += int(shard.size)

    # -- per-object re-replication (NameNode block repair) ----------------------

    @_io_locked
    def re_replicate(self, layout: ObjectLayout, from_node: int,
                     to_node: int) -> int:
        """Copy one replica of a replicated object onto ``to_node`` and
        repoint ``from_node``'s slot — the per-block analogue of
        :meth:`repair_node`, driven by *detected* failures: the
        :class:`repro.namenode.BlockReplicator` calls this per
        under-replicated block, so only blocks a view change actually
        touched move (not the whole node's contents).  The bytes come
        from a surviving replica through the authenticated read path;
        the write goes through the policy engine like any client write.
        Returns the bytes copied."""
        if layout.resiliency != Resiliency.REPLICATION:
            raise ValueError(
                f"object {layout.object_id}: re_replicate handles "
                f"replicated objects; EC shards go through repair_node"
            )
        if to_node in self.failed or to_node in self.meta.suspected:
            raise ValueError(f"target node {to_node} is not live")
        idx = next(
            (i for i, c in enumerate(layout.data_coords)
             if c.node == from_node),
            None,
        )
        if idx is None:
            raise ValueError(
                f"object {layout.object_id} has no replica on {from_node}")
        data = None
        for coord in layout.data_coords:
            if coord.node == from_node:
                continue
            data = self._read_shard(coord, layout.size)
            if data is not None:
                break
        if data is None:
            layout.lost = True
            raise IOError(
                f"object {layout.object_id}: no live replica to copy from")
        addr = self.meta._extent(to_node, layout.size)
        coord = ReplicaCoord(to_node, addr)
        self.client.write(self.capability, data, [coord])
        self._set_coord(layout, idx, coord)
        return layout.size

    # -- conservation audit -----------------------------------------------------

    def audit(self) -> dict:
        """Byte-conservation ledger under failure injection: every byte
        written is *readable* (all data shards / a replica live),
        *reconstructable* (EC with <= m shards lost), or *lost* (beyond
        the policy's tolerance) — the three buckets partition
        ``bytes_written`` exactly, so nothing goes silently missing.
        ``read_retries`` / ``read_timeouts`` account the live-loss
        plane: extra shard-read attempts a lossy link forced, and shards
        given up on after the bounded budget."""
        out = {"objects": 0, "bytes_written": 0, "readable_bytes": 0,
               "reconstructable_bytes": 0, "lost_bytes": 0,
               "read_retries": self.read_retries,
               "read_timeouts": self.read_timeouts}
        for layout in self.meta._objects.values():
            out["objects"] += 1
            out["bytes_written"] += layout.size
            if layout.lost:
                # pinned by repair: a re-provisioned node's zeroed shards
                # must never count as readable
                out["lost_bytes"] += layout.size
                continue
            if layout.resiliency == Resiliency.ERASURE_CODING:
                # an empty internal block is known zeros, on any node
                up = [c.node not in self.failed or not length for c, length
                      in zip(self._layout_coords(layout), layout.slot_lens)]
                live = sum(up)
                data_live = all(up[: layout.ec_k])
                if data_live:
                    out["readable_bytes"] += layout.size
                elif live >= layout.ec_k:
                    out["reconstructable_bytes"] += layout.size
                else:
                    out["lost_bytes"] += layout.size
            else:
                if any(c.node not in self.failed
                       for c in layout.data_coords):
                    out["readable_bytes"] += layout.size
                else:
                    out["lost_bytes"] += layout.size
        assert (out["readable_bytes"] + out["reconstructable_bytes"]
                + out["lost_bytes"]) == out["bytes_written"]
        return out

    def stats(self) -> dict:
        return {
            "nodes": self.num_nodes,
            "failed": sorted(self.failed),
            "bytes_stored": sum(n.storage.bytes_written for n in self.nodes),
            "packets": self.router.packets_delivered,
            "objects": len(self.meta._objects),
        }

    # -- durability: spill node contents + metadata to disk --------------------

    def spill(self, dirname: str | None = None) -> str:
        """Persist every node's storage and the object namespace to disk
        (one file per node + a metadata pickle); survives process restart."""
        import pickle

        d = dirname or self.spill_dir
        if d is None:
            raise ValueError("no spill directory configured")
        os.makedirs(d, exist_ok=True)
        for node in self.nodes:
            node.storage.mem.tofile(os.path.join(d, f"node{node.node_id}.bin"))
        with open(os.path.join(d, "meta.pkl"), "wb") as f:
            pickle.dump(
                {
                    "objects": self.meta._objects,
                    "alloc": self.meta._alloc,
                    "next_oid": self.meta._next_oid,
                    "key": bytes(self.meta.authority.key.tobytes()),
                    "num_nodes": self.num_nodes,
                    "capacity": self.node_capacity,
                },
                f,
            )
        return d

    @classmethod
    def from_spill(cls, dirname: str, client_id: int = 1) -> "StorageCluster":
        """Reconstruct a cluster (nodes + namespace + auth key) from disk."""
        import pickle

        with open(os.path.join(dirname, "meta.pkl"), "rb") as f:
            meta = pickle.load(f)
        cluster = cls(meta["num_nodes"], meta["capacity"], client_id=client_id,
                      spill_dir=dirname)
        cluster.meta.authority = CapabilityAuthority(meta["key"])
        for node in cluster.nodes:
            node.authority = cluster.meta.authority
            path = os.path.join(dirname, f"node{node.node_id}.bin")
            node.storage.mem[:] = np.fromfile(path, dtype=np.uint8)
        cluster.meta._objects = meta["objects"]
        cluster.meta._alloc = meta["alloc"]
        cluster.meta._next_oid = meta["next_oid"]
        cluster.capability = cluster.meta.issue_capability(client_id)
        return cluster
