"""CheckpointManager: async, sharded, policy-protected training checkpoints.

Maps a JAX pytree (params + optimizer state) onto the DFS storage cluster:
every leaf is serialized, split into stripe objects, and written under a
resiliency policy — RS(k, m) erasure coding (storage-efficient, survives m
node losses) or k-way replication (ring/PBT).  Writes run on a background
thread (async checkpointing overlaps the next train steps); ``restore``
reads back with degraded-mode reconstruction and verifies integrity with
the capability MAC of each manifest entry.

The manifest itself (tiny) is written with max replication to all nodes.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

import numpy as np

from repro.checkpoint.storage import StorageCluster
from repro.core.auth import sponge_mac
from repro.core.packets import ReplStrategy, Resiliency
from repro.policy.functional import write_plan
from repro.policy.spec import PolicySpec, RS, SpongeAuth, Tree
from repro.trace import wall


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    resiliency: Resiliency = Resiliency.ERASURE_CODING
    k: int = 4
    m: int = 2
    strategy: ReplStrategy = ReplStrategy.RING
    stripe_bytes: int = 1 << 20       # split big leaves into stripe objects
    #: EC encode locus: "client" batches every stripe of a leaf through one
    #: RSCode.encode_stripes call (PR 2's fused data plane) and writes the
    #: shards as authenticated plain writes; "nic" streams per-packet
    #: intermediate parities through the policy engine (paper section VI).
    encode: str = "client"

    def spec(self) -> PolicySpec:
        """The equivalent declarative policy (single source of truth —
        ``from_spec`` round-trips)."""
        if self.resiliency == Resiliency.ERASURE_CODING:
            engine = "client" if self.encode == "client" else "spin"
            return PolicySpec(
                "spin", SpongeAuth(), erasure=RS(self.k, self.m, engine),
                name="checkpoint-ec",
            )
        if self.resiliency == Resiliency.REPLICATION:
            return PolicySpec(
                "spin", SpongeAuth(), replication=Tree(self.k, self.strategy),
                name="checkpoint-repl",
            )
        return PolicySpec("spin", SpongeAuth(), name="checkpoint-plain")

    @classmethod
    def from_spec(
        cls, spec: PolicySpec, stripe_bytes: int = 1 << 20
    ) -> "CheckpointPolicy":
        plan = write_plan(spec)
        if plan.kind == "flat":
            # Flat has no object layout; silently storing one copy would
            # drop the requested redundancy.
            raise ValueError(
                "Flat replication has no checkpoint layout; use a Tree spec"
            )
        if plan.resiliency == Resiliency.ERASURE_CODING:
            return cls(
                Resiliency.ERASURE_CODING, plan.k, plan.m,
                stripe_bytes=stripe_bytes,
                encode="client" if plan.kind == "ec-client" else "nic",
            )
        if plan.resiliency == Resiliency.REPLICATION:
            return cls(
                Resiliency.REPLICATION, plan.k, 0, plan.strategy,
                stripe_bytes=stripe_bytes,
            )
        return cls(Resiliency.NONE, 1, 0, stripe_bytes=stripe_bytes)


def _leaf_to_bytes(x) -> tuple[bytes, dict]:
    arr = np.asarray(x)
    meta = {"dtype": str(arr.dtype), "shape": list(arr.shape)}
    return arr.tobytes(), meta


def _bytes_to_leaf(raw: bytes, meta: dict) -> np.ndarray:
    return np.frombuffer(raw, dtype=np.dtype(meta["dtype"])).reshape(
        meta["shape"]
    )


class CheckpointManager:
    def __init__(
        self,
        cluster: StorageCluster,
        policy: CheckpointPolicy | PolicySpec | None = None,
    ):
        self.cluster = cluster
        if isinstance(policy, PolicySpec):
            policy = CheckpointPolicy.from_spec(policy)
        self.policy = policy or CheckpointPolicy()
        self._manifests: dict[int, dict] = {}
        self._pending: threading.Thread | None = None
        self._lock = threading.Lock()
        #: per completed save, seconds from ``save()``'s entry (snapshot
        #: included) to its manifest, less the wait for the previous
        #: save's writer: the ``ckpt.save`` span's time less its
        #: ``ckpt.wait`` child's
        self.save_seconds: list[float] = []
        #: saves that raised, in the snapshot or on the writer thread
        #: (their ``ckpt.save`` span is marked failed)
        self.failed_saves = 0

    # -- save -------------------------------------------------------------------

    def save(self, step: int, tree: Any, blocking: bool = False) -> None:
        """Snapshot on the caller thread, write on a background thread."""
        import jax

        t0 = time.perf_counter_ns()
        root = wall.begin("ckpt.save", "entry", t0)
        parent = wall.parent_of(root)
        try:
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            # materialize to host now so training can mutate its arrays
            with wall.span("ckpt.snapshot", "entry", parent):
                snap = [(self._path_str(p), np.asarray(leaf))
                        for p, leaf in flat]
            waited = self._wait_previous(parent)
        except BaseException:
            self._failed(root)
            raise

        def worker():
            try:
                manifest = self._write_leaves(step, snap, parent)
            except BaseException:
                self._failed(root)
                raise
            with self._lock:
                self._manifests[step] = manifest
            t1 = time.perf_counter_ns()
            wall.end(root, t1=t1)
            self.save_seconds.append((t1 - t0 - waited) / 1e9)

        self._pending = threading.Thread(target=worker, daemon=True)
        self._pending.start()
        if blocking:
            self.wait()

    def _wait_previous(self, parent) -> int:
        """Wait for the previous save's writer, under a ``ckpt.wait``
        span; returns the nanoseconds waited."""
        w0 = time.perf_counter_ns()
        live = wall.begin("ckpt.wait", "entry", w0, parent)
        self.wait()
        w1 = time.perf_counter_ns()
        wall.end(live, t1=w1)
        return w1 - w0

    def _failed(self, root) -> None:
        self.failed_saves += 1
        wall.end(root, failed=True)

    def _write_leaves(self, step: int, snap: list, parent) -> dict:
        """Write every leaf of a snapshot; returns the save's manifest."""
        pol = self.policy
        bulk_ec = (pol.resiliency == Resiliency.ERASURE_CODING
                   and pol.encode == "client")
        manifest = {"step": step, "leaves": [], "policy": {
            "resiliency": int(pol.resiliency),
            "k": pol.k, "m": pol.m, "encode": pol.encode,
        }}
        for path, arr in snap:
            with wall.span("ckpt.leaf", "entry", parent):
                manifest["leaves"].append(self._write_leaf(path, arr, bulk_ec))
        return manifest

    def _write_leaf(self, path: str, arr: np.ndarray, bulk_ec: bool) -> dict:
        pol = self.policy
        raw, meta = _leaf_to_bytes(arr)
        blobs = [
            raw[off : off + pol.stripe_bytes]
            for off in range(0, max(len(raw), 1), pol.stripe_bytes)
        ]
        if bulk_ec:
            # one batched RSCode.encode_stripes per chunk-length
            # group across all stripes of this leaf
            layouts = self.cluster.write_object_bulk(blobs, k=pol.k, m=pol.m)
        else:
            layouts = [
                self.cluster.write_object(
                    blob,
                    resiliency=pol.resiliency,
                    k=pol.k,
                    m=pol.m,
                    strategy=pol.strategy,
                )
                for blob in blobs
            ]
        stripes = [
            {"oid": layout.object_id, "size": len(blob)}
            for layout, blob in zip(layouts, blobs)
        ]
        with wall.span("ckpt.mac", "entry"):
            mac = sponge_mac(
                np.frombuffer(raw[:64].ljust(64, b"\0"), np.uint32),
                self.cluster.meta.authority.key,
            )
        return {"path": path, "meta": meta, "stripes": stripes,
                "mac": [int(mac[0]), int(mac[1])], "bytes": len(raw)}

    def wait(self) -> None:
        if self._pending is not None and self._pending.is_alive():
            self._pending.join()

    # -- restore ------------------------------------------------------------------

    def latest_step(self) -> int | None:
        with self._lock:
            return max(self._manifests) if self._manifests else None

    def restore(self, step: int | None = None, treedef: Any = None) -> Any:
        """Read back a checkpoint (degraded-mode capable); returns a pytree
        when ``treedef`` (from tree_flatten_with_path of a template) is
        given, else {path: array}."""
        self.wait()
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError("no checkpoints saved")
        manifest = self._manifests[step]
        out: dict[str, np.ndarray] = {}
        for leaf in manifest["leaves"]:
            # All stripes of the leaf read (and, degraded, reconstructed)
            # together: read_objects batches every same-pattern stripe
            # through ONE RSCode.decode_stripes call.
            layouts = [self.cluster.meta.lookup(s["oid"])
                       for s in leaf["stripes"]]
            raws = self.cluster.read_objects(layouts)
            raw = b"".join(
                raw[: stripe["size"]]
                for raw, stripe in zip(raws, leaf["stripes"])
            )
            mac = sponge_mac(
                np.frombuffer(raw[:64].ljust(64, b"\0"), np.uint32),
                self.cluster.meta.authority.key,
            )
            if [int(mac[0]), int(mac[1])] != leaf["mac"]:
                raise IOError(f"integrity check failed for {leaf['path']}")
            out[leaf["path"]] = _bytes_to_leaf(raw, leaf["meta"])
        if treedef is None:
            return out
        import jax

        flat, td = jax.tree_util.tree_flatten_with_path(treedef)
        leaves = [out[self._path_str(p)] for p, _ in flat]
        return jax.tree_util.tree_unflatten(td, leaves)

    @staticmethod
    def _path_str(path) -> str:
        parts = []
        for k in path:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        return "/".join(parts)
