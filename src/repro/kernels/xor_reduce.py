"""XOR-fold Pallas kernel: the parity-node accumulator aggregation.

Paper section VI-B3: parity nodes XOR k intermediate parity streams into
pool accumulators (p_i^0 ^ p_i^1 ^ ... ^ p_i^{k-1}).  On TPU the fold over
the stream axis is a single VMEM-tiled pass: each grid step loads a
(n, block_w) tile and folds the n rows with a log-depth XOR tree, keeping
the lane dimension fully vectorized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _xor_reduce_batched_kernel(x_ref, out_ref, *, n: int):
    """(1, n, block_w) tile -> (1, 1, block_w): log-depth XOR fold of the n
    rows (better ILP than a serial fold), each row a static ref slice."""
    vals = [x_ref[0, pl.ds(i, 1), :] for i in range(n)]
    while len(vals) > 1:
        nxt = [vals[i] ^ vals[i + 1] for i in range(0, len(vals) - 1, 2)]
        if len(vals) % 2:
            nxt.append(vals[-1])
        vals = nxt
    out_ref[0] = vals[0]


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def xor_reduce_batched(
    x: jax.Array, *, block_w: int = 2048, interpret: bool
) -> jax.Array:
    """Batched XOR fold: (S, n, w) uint32 over axis 1 -> (S, w) uint32.

    One dispatch over a 2D (batch, word-block) grid — the parity-node
    aggregation for S concurrent sequences in a single kernel launch.
    w % block_w == 0.  The output block is (1, 1, block_w) of an (S, 1, w)
    array, so its last two dimensions meet the TPU's (8, 128) tiling rule.
    """
    s, n, w = x.shape
    assert w % block_w == 0, (w, block_w)
    grid = (s, w // block_w)
    return pl.pallas_call(
        functools.partial(_xor_reduce_batched_kernel, n=n),
        grid=grid,
        in_specs=[pl.BlockSpec((1, n, block_w), lambda si, wi: (si, 0, wi))],
        out_specs=pl.BlockSpec((1, 1, block_w), lambda si, wi: (si, 0, wi)),
        out_shape=jax.ShapeDtypeStruct((s, 1, w), jnp.uint32),
        interpret=interpret,
    )(x)[:, 0]
