"""Data replication policy: ring / pipelined-binary-tree broadcast.

Paper section V: replication on ``k`` storage nodes is a broadcast along a
client-chosen virtual topology (ring or pipelined binary tree, "PBT"),
source-routed via replica coordinates in the write-request header, and —
this is the contribution — *pipelined at packet granularity* by the NIC
handlers: each node forwards every packet to its children as it arrives,
so the broadcast costs (depth + n_packets - 1) packet times instead of
depth * message time.

TPU adaptation: per-packet ring forwarding over the ICI torus *is*
``lax.ppermute`` with chunk pipelining.  :func:`ring_broadcast` and
:func:`pbt_broadcast` implement the schedules as `shard_map`-compatible
collectives with a tunable chunk count — used by the checkpoint data plane
to replicate state shards across data-parallel peers and benchmarked in the
perf pass.  :class:`BroadcastPlan` is the host-side planner shared with the
functional DFS node and the simulator.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.packets import ReplStrategy

# ---------------------------------------------------------------------------
# Host-side schedule planner (shared by handlers.py and sim/).
# ---------------------------------------------------------------------------


def children_of(rank: int, k: int, strategy: ReplStrategy) -> list[int]:
    """Children of virtual rank ``rank`` in a broadcast over ranks [0, k)."""
    if strategy == ReplStrategy.RING:
        return [rank + 1] if rank + 1 < k else []
    if strategy == ReplStrategy.PBT:
        return [c for c in (2 * rank + 1, 2 * rank + 2) if c < k]
    raise ValueError(f"unknown strategy {strategy}")


def depth_of(rank: int, strategy: ReplStrategy) -> int:
    if strategy == ReplStrategy.RING:
        return rank
    return int(math.floor(math.log2(rank + 1))) if rank > 0 else 0


def tree_depth(k: int, strategy: ReplStrategy) -> int:
    return max(depth_of(r, strategy) for r in range(k))


@dataclasses.dataclass(frozen=True)
class BroadcastPlan:
    """Broadcast schedule over ``k`` replicas with ``num_chunks`` chunks."""

    strategy: ReplStrategy
    k: int
    num_chunks: int

    @property
    def arity(self) -> int:
        return 1 if self.strategy == ReplStrategy.RING else 2

    @property
    def depth(self) -> int:
        return tree_depth(self.k, self.strategy)

    @property
    def num_rounds(self) -> int:
        """Rounds until the deepest node holds the last chunk."""
        return self.num_chunks + self.depth - 1 if self.k > 1 else 0

    def children(self, rank: int) -> list[int]:
        return children_of(rank, self.k, self.strategy)

    def logg_p_latency(
        self,
        chunk_bytes: int,
        bandwidth_Bps: float,
        overhead_s: float,
        hop_latency_s: float,
    ) -> float:
        """LogGP-style pipelined broadcast latency estimate (paper refs
        [33], [34]).  Per round a node serializes ``arity`` copies of one
        chunk; the pipeline drains after ``num_rounds`` rounds.
        """
        if self.k <= 1:
            return 0.0
        per_round = self.arity * chunk_bytes / bandwidth_Bps + overhead_s
        return self.num_rounds * per_round + self.depth * hop_latency_s


def optimal_chunk_count(
    size_bytes: int,
    k: int,
    strategy: ReplStrategy,
    bandwidth_Bps: float,
    overhead_s: float,
    max_chunks: int = 4096,
) -> int:
    """Minimize LogGP latency over the chunk count (closed form + clamp).

    d(latency)/dC = 0 at C* = sqrt(depth * S/B / overhead) for arity a:
    latency(C) = (C + d - 1)(a*S/(C*B) + o).
    """
    depth = tree_depth(k, strategy)
    if depth == 0 or size_bytes == 0:
        return 1
    a = 1 if strategy == ReplStrategy.RING else 2
    ser = a * size_bytes / bandwidth_Bps
    c_star = math.sqrt(max(depth - 1, 1) * ser / max(overhead_s, 1e-12))
    return max(1, min(max_chunks, int(round(c_star)), size_bytes))


# ---------------------------------------------------------------------------
# JAX data plane: chunk-pipelined broadcast collectives (shard_map bodies).
# ---------------------------------------------------------------------------


def _floor_log2(x: jax.Array) -> jax.Array:
    """floor(log2(x)) for positive int32 x, computed with bit twiddling."""
    x = x.astype(jnp.int32)
    r = jnp.zeros_like(x)
    for shift in (16, 8, 4, 2, 1):
        hit = (x >> shift) > 0
        r = jnp.where(hit, r + shift, r)
        x = jnp.where(hit, x >> shift, x)
    return r


def _pipelined_broadcast(
    x: jax.Array,
    axis_name: str,
    num_chunks: int,
    strategy: ReplStrategy,
    axis_size: int,
) -> jax.Array:
    """Shared body: pipelined broadcast from rank 0 along ``axis_name``.

    ``x`` is the (identically-shaped) local view on every rank; only rank
    0's content is broadcast.  Leading dim must divide into ``num_chunks``.
    Runs ``num_chunks + depth - 1`` ppermute rounds of one chunk each —
    the collective realization of per-packet forwarding.
    """
    n = axis_size
    idx = lax.axis_index(axis_name)
    flat = x.reshape(num_chunks, -1)
    c = num_chunks

    if strategy == ReplStrategy.RING:
        perms = [[(i, i + 1) for i in range(n - 1)]]
        depth_me = idx
        max_depth = n - 1
    else:
        # jax.lax.ppermute is a strict (partial) permutation — no multicast —
        # so the binary tree is two permutations per round: one to left
        # children (odd ranks), one to right children (even ranks).  Two
        # sends per chunk per node is exactly PBT's arity-2 bandwidth cost
        # (paper: sPIN-PBT sustains half the goodput of sPIN-Ring).
        perms = [
            [(v, 2 * v + 1) for v in range(n) if 2 * v + 1 < n],
            [(v, 2 * v + 2) for v in range(n) if 2 * v + 2 < n],
        ]
        depth_me = _floor_log2(idx + 1)
        max_depth = int(math.floor(math.log2(n))) if n > 1 else 0

    num_rounds = c + max_depth - 1 if n > 1 else 0
    is_root = idx == 0

    def body(r, carry):
        buf, cur = carry
        root_chunk = lax.dynamic_index_in_dim(
            flat, jnp.clip(r, 0, c - 1), axis=0, keepdims=False
        )
        send = jnp.where(is_root, root_chunk, cur)
        if len(perms) == 1:
            recv = lax.ppermute(send, axis_name, perms[0])
        else:
            recv_l = lax.ppermute(send, axis_name, perms[0])
            recv_r = lax.ppermute(send, axis_name, perms[1])
            recv = jnp.where(idx % 2 == 1, recv_l, recv_r)
        # Non-root at depth d receives chunk (r - d + 1) at round r.
        recv_idx = r - depth_me + 1
        valid = (~is_root) & (recv_idx >= 0) & (recv_idx < c)
        upd = lax.dynamic_update_index_in_dim(
            buf, recv, jnp.clip(recv_idx, 0, c - 1), axis=0
        )
        buf = jnp.where(valid, upd, buf)
        return buf, recv

    init = (jnp.where(is_root, flat, jnp.zeros_like(flat)), jnp.zeros_like(flat[0]))
    buf, _ = lax.fori_loop(0, num_rounds, body, init)
    return buf.reshape(x.shape)


def ring_broadcast(
    x: jax.Array, axis_name: str, num_chunks: int, axis_size: int
) -> jax.Array:
    """Chunk-pipelined ring broadcast from rank 0 (sPIN-Ring analogue)."""
    return _pipelined_broadcast(x, axis_name, num_chunks, ReplStrategy.RING, axis_size)


def pbt_broadcast(
    x: jax.Array, axis_name: str, num_chunks: int, axis_size: int
) -> jax.Array:
    """Chunk-pipelined binary-tree broadcast from rank 0 (sPIN-PBT)."""
    return _pipelined_broadcast(x, axis_name, num_chunks, ReplStrategy.PBT, axis_size)


def replicate(
    x: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str,
    strategy: ReplStrategy = ReplStrategy.RING,
    num_chunks: int = 8,
) -> jax.Array:
    """Public entry: broadcast rank-0's ``x`` to all ranks of ``axis_name``.

    Returns an array where every shard along ``axis_name`` holds rank-0's
    data (i.e. k-way replication of a state shard across peers).
    """
    from jax.sharding import PartitionSpec as P

    axis_size = mesh.shape[axis_name]
    fn = partial(
        _pipelined_broadcast,
        axis_name=axis_name,
        num_chunks=num_chunks,
        strategy=strategy,
        axis_size=axis_size,
    )
    spec = P(axis_name)
    # under jit: an eager shard_map call on a mesh with Explicit axes (what
    # jax.make_mesh builds) is placed on one device and refused
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec)
    )(x)
