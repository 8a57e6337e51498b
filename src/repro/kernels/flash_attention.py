"""Pallas TPU flash-attention (forward) kernel.

The training hot spot: online-softmax attention tiled for VMEM/MXU.  Grid
is (batch*heads, q_blocks, kv_blocks); the kv dimension is the innermost
(sequential) grid axis, accumulating into VMEM scratch (acc, m, l) and
writing the output tile on the last kv step — the per-packet streaming
aggregation of the paper's handlers, on the systolic array.

GQA without materializing repeated KV heads: the K/V BlockSpec index maps
fold the query head onto its kv group (``h // rep``), so each kv head's
tile is streamed once per query-group instead of being physically
repeated.

Block shapes default to (128, head_dim) q-tiles x (512, head_dim) kv-tiles
— MXU-aligned (matmul dims multiples of 128) with a VMEM working set of
~(bq*D + 2*bk*D + bq*Dv) * 2-4 B (< 1 MiB at D=128).  Validated in
interpret mode against the jnp reference across shape sweeps
(tests/test_kernels.py); the jnp blockwise path in models/attention.py is
the CPU/backward implementation, this kernel is the TPU-forward
replacement.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, bq: int, bk: int, nk: int, seq: int,
):
    j = pl.program_id(1)           # q block
    kk = pl.program_id(2)          # kv block (innermost, sequential)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                   # (bq, d)
    k = k_ref[0]                   # (bk, d)
    v = v_ref[0]                   # (bk, dv)
    scores = jax.lax.dot_general(
        q.astype(jnp.float32) * scale, k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
    )                              # (bq, bk)
    q_pos = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kv_pos < seq
    if causal:
        mask = mask & (kv_pos <= q_pos)
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
    ).astype(jnp.float32)
    m_ref[...] = m_new

    @pl.when(kk == nk - 1)
    def _finish():
        ll = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / ll[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention_fwd(
    q: jax.Array,      # (B, S, H, D)
    k: jax.Array,      # (B, S, Hkv, D)
    v: jax.Array,      # (B, S, Hkv, Dv)
    causal: bool = True,
    bq: int = 128,
    bk: int = 512,
    *,
    interpret: bool,
) -> jax.Array:
    b, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    rep = h // hkv
    scale = 1.0 / math.sqrt(d)
    bq = min(bq, s)
    bk = min(bk, s)
    nq = -(-s // bq)
    nk = -(-s // bk)
    # fold heads into the leading grid dim
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, dv)
    if nq * bq != s:
        qh = jnp.pad(qh, ((0, 0), (0, nq * bq - s), (0, 0)))
    if nk * bk != s:
        kh = jnp.pad(kh, ((0, 0), (0, nk * bk - s), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, nk * bk - s), (0, 0)))

    def kv_head(i):
        # query row i = b*h + hq  ->  kv row = b*hkv + hq // rep
        return (i // h) * hkv + (i % h) // rep

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
            nk=nk, seq=s,
        ),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (kv_head(i), kk, 0)),
            pl.BlockSpec((1, bk, dv), lambda i, j, kk: (kv_head(i), kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * bq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out = out[:, :s, :]
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
