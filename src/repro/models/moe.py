"""Mixture-of-Experts layer: top-k routing with capacity-bounded dispatch.

Dispatch is scatter-based (sort-free ranks via cumulative counts): tokens
are placed into a fixed (E, C, d) buffer, expert FFNs run as one batched
einsum over the expert axis, and results are gathered back with router
weights.  Expert-parallelism comes from sharding the expert axis over the
``model`` mesh axis (GSPMD inserts the dispatch/combine collectives); the
token axes remain batch/sequence-sharded.  Tokens over capacity are dropped
(standard Switch/GShard semantics, capacity_factor 1.25 default).

Supports shared experts (DeepSeek-V2: 2 shared + 64 routed top-6) and pure
routed (DBRX: 16 routed top-4).
"""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init, swiglu_apply, swiglu_init


def moe_init(
    key,
    d_model: int,
    d_ff_expert: int,
    n_experts: int,
    n_shared: int = 0,
    d_ff_shared: int | None = None,
) -> Params:
    kr, ke, ks = jax.random.split(key, 3)
    scale = 1.0 / math.sqrt(d_model)
    kw1, kw2, kw3 = jax.random.split(ke, 3)
    p: Params = {
        "router": dense_init(kr, d_model, n_experts, scale=0.02),
        # stacked expert weights (E, d, ff) / (E, ff, d)
        "w_gate": jax.random.normal(kw1, (n_experts, d_model, d_ff_expert)) * scale,
        "w_up": jax.random.normal(kw2, (n_experts, d_model, d_ff_expert)) * scale,
        "w_down": jax.random.normal(kw3, (n_experts, d_ff_expert, d_model))
        * (1.0 / math.sqrt(d_ff_expert)),
    }
    if n_shared:
        p["shared"] = swiglu_init(
            ks, d_model, (d_ff_shared or d_ff_expert) * n_shared
        )
    return p


def moe_apply(
    p: Params,
    x: jax.Array,                   # (B, S, d)
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    ep_spec=None,                   # PartitionSpec for the (E, C, d) buffer
    dense_fallback: bool = False,
) -> jax.Array:
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    topk_p, topk_i = jax.lax.top_k(probs, top_k)                # (T, K)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    if dense_fallback:
        # Tiny-config smoke path: weight every expert densely (exact modulo
        # capacity dropping); O(E/topk) more FLOPs — never used at scale.
        weights = jnp.zeros((t, n_experts), jnp.float32)
        weights = weights.at[jnp.arange(t)[:, None], topk_i].add(topk_p)
        h = jnp.einsum("td,edf->tef", xf.astype(jnp.bfloat16),
                       p["w_gate"].astype(jnp.bfloat16))
        u = jnp.einsum("td,edf->tef", xf.astype(jnp.bfloat16),
                       p["w_up"].astype(jnp.bfloat16))
        y = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u,
                       p["w_down"].astype(jnp.bfloat16))
        out = jnp.einsum("ted,te->td", y, weights.astype(jnp.bfloat16))
    else:
        # Per-row (per-example) dispatch: routing, ranking and the capacity
        # buffer are computed independently per batch row, so every step is
        # batch-preserving — the batch axis stays data-sharded end to end
        # and the only cross-shard movement is the (batch <-> expert)
        # redistribution of the dispatch buffer (a clean all-to-all), not
        # the global-sort all-reduce storm of a flat-token formulation.
        # (GShard-style per-group capacity; group = one sequence.)
        L = s * top_k
        capacity = max(1, int(s * top_k / n_experts * capacity_factor))
        p_row = topk_p.reshape(b, L)                            # (B, L)
        e_row = topk_i.reshape(b, L)                            # (B, L)
        order = jnp.argsort(e_row, axis=1, stable=True)         # per-row sort
        sorted_e = jnp.take_along_axis(e_row, order, axis=1)
        counts = jax.nn.one_hot(e_row, n_experts, dtype=jnp.int32).sum(axis=1)
        starts = jnp.cumsum(counts, axis=1) - counts            # (B, E)
        ranks_sorted = (
            jnp.arange(L)[None, :]
            - jnp.take_along_axis(starts, sorted_e, axis=1)
        )
        b_ix = jnp.arange(b)[:, None]
        pos = jnp.zeros((b, L), jnp.int32).at[b_ix, order].set(
            ranks_sorted.astype(jnp.int32)
        )
        keep = pos < capacity
        slot = e_row * capacity + jnp.where(keep, pos, 0)       # (B, L)
        x_rows = x.reshape(b, s, 1, d)
        contrib = jnp.where(
            keep[..., None],
            jnp.broadcast_to(x_rows, (b, s, top_k, d)).reshape(b, L, d)
            .astype(jnp.bfloat16),
            0,
        )
        buffer = (
            jnp.zeros((b, n_experts * capacity, d), jnp.bfloat16)
            .at[b_ix, slot]
            .add(contrib, mode="drop")
        ).reshape(b, n_experts, capacity, d)
        if ep_spec is not None:
            buffer = jax.lax.with_sharding_constraint(buffer, ep_spec)
        g = jnp.einsum("becd,edf->becf", buffer, p["w_gate"].astype(jnp.bfloat16))
        u = jnp.einsum("becd,edf->becf", buffer, p["w_up"].astype(jnp.bfloat16))
        y = jnp.einsum(
            "becf,efd->becd", jax.nn.silu(g) * u,
            p["w_down"].astype(jnp.bfloat16),
        )
        if ep_spec is not None:
            y = jax.lax.with_sharding_constraint(y, ep_spec)
        y_flat = y.reshape(b, n_experts * capacity, d)
        gathered = jnp.take_along_axis(y_flat, slot[..., None], axis=1)
        per_choice = gathered * (
            keep[..., None] * p_row[..., None]
        ).astype(jnp.bfloat16)
        out = per_choice.reshape(b, s, top_k, d).sum(axis=2).reshape(t, d)

    if "shared" in p:
        out = out + swiglu_apply(p["shared"], xf)
    return out.reshape(b, s, d).astype(x.dtype)


def moe_ep_apply(
    p: Params,
    x: jax.Array,                   # (B, S, d) — B over data, S over model
    n_experts: int,
    top_k: int,
    capacity_factor: float,
    mesh,
    data_axes: tuple[str, ...],
    model_axis: str,
) -> jax.Array:
    """Expert parallelism as an explicit shard_map dataflow.

    GSPMD lowers token->expert scatters against an expert-sharded buffer by
    replicating the buffer (TB-scale all-gathers/all-reduces at dbrx size).
    This path is the canonical manual EP instead: per-device local routing
    and capacity buffers (zero collectives), one all-to-all to the expert
    owners, local FFN, one all-to-all back — the paper's one-sided
    principle: the request carries everything needed, data moves directly
    to its target with no global coordination.

    Expert weights stay FSDP-sharded (E over model, d/ff over data) and are
    all-gathered over the data axes per layer inside the region.
    """
    import math as _math

    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[model_axis]
    e_loc = n_experts // tp
    assert e_loc * tp == n_experts

    def local_fn(xl, rw, wg, wu, wd):
        # gather the FSDP shards of this device's experts
        rw = jax.lax.all_gather(rw, data_axes, axis=0, tiled=True)
        wg = jax.lax.all_gather(wg, data_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, data_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, data_axes, axis=2, tiled=True)
        bl, sl, d = xl.shape
        t = bl * sl
        xf = xl.reshape(t, d)
        logits = xf.astype(jnp.float32) @ rw.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_i = jax.lax.top_k(probs, top_k)
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
        L = t * top_k
        cap = max(1, int(_math.ceil(t * top_k / n_experts * capacity_factor)))
        flat_e = topk_i.reshape(L)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.zeros((n_experts,), jnp.int32).at[flat_e].add(1)
        starts = jnp.cumsum(counts) - counts
        ranks_sorted = jnp.arange(L, dtype=jnp.int32) - starts[sorted_e]
        pos = jnp.zeros((L,), jnp.int32).at[order].set(ranks_sorted)
        keep = pos < cap
        slot = flat_e * cap + jnp.where(keep, pos, 0)
        tok_of = jnp.arange(L) // top_k
        contrib = jnp.where(
            keep[:, None], xf[tok_of].astype(jnp.bfloat16), 0
        )
        buffer = (
            jnp.zeros((n_experts * cap, d), jnp.bfloat16)
            .at[slot]
            .add(contrib, mode="drop")
        )
        # -> expert owners: (tp, e_loc*cap, d) blocks, one per peer
        recv = jax.lax.all_to_all(
            buffer.reshape(tp * e_loc * cap, d), model_axis, 0, 0, tiled=True
        )
        h = (
            recv.reshape(tp, e_loc, cap, d)
            .transpose(1, 0, 2, 3)
            .reshape(e_loc, tp * cap, d)
        )
        g = jnp.einsum("ecd,edf->ecf", h, wg.astype(jnp.bfloat16))
        u = jnp.einsum("ecd,edf->ecf", h, wu.astype(jnp.bfloat16))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                       wd.astype(jnp.bfloat16))
        back = (
            y.reshape(e_loc, tp, cap, d)
            .transpose(1, 0, 2, 3)
            .reshape(tp * e_loc * cap, d)
        )
        y_home = jax.lax.all_to_all(back, model_axis, 0, 0, tiled=True)
        per_choice = y_home[slot] * (
            keep[:, None] * topk_p.reshape(L)[:, None]
        ).astype(jnp.bfloat16)
        out = jax.ops.segment_sum(per_choice, tok_of, num_segments=t)
        return out.reshape(bl, sl, d).astype(xl.dtype)

    d_axes = tuple(data_axes)
    x_spec = P(d_axes, model_axis, None)
    out = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(d_axes, None),                    # router (d, E)
            P(model_axis, d_axes, None),        # w_gate (E, d, ff)
            P(model_axis, d_axes, None),        # w_up
            P(model_axis, None, d_axes),        # w_down (E, ff, d)
        ),
        out_specs=x_spec,
    )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    if "shared" in p:
        b, s, d = x.shape
        out = out + swiglu_apply(p["shared"], x.reshape(b * s, d)).reshape(
            b, s, d
        ).astype(x.dtype)
    return out


def moe_flops_per_token(
    d_model: int, d_ff_expert: int, top_k: int, n_shared: int = 0,
    d_ff_shared: int | None = None,
) -> int:
    """Active-parameter matmul FLOPs per token (fwd), for 6*N_active*D."""
    routed = top_k * 3 * 2 * d_model * d_ff_expert
    shared = n_shared * 3 * 2 * d_model * (d_ff_shared or d_ff_expert)
    return routed + shared
