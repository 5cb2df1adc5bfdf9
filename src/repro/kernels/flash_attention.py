"""Flash attention (causal / local-window / bidirectional) for TPU.

Online-softmax tiling: grid (batch, q_heads, q_blocks, kv_blocks) with the
kv dimension innermost (sequential on TPU), fp32 accumulator + running
max/sum in VMEM scratch.  Block sizes default to (128, 128) — MXU-aligned —
and q/k/v tiles stream HBM->VMEM per BlockSpec.  The wrapper moves heads
ahead of the sequence, so each tile's last two dims are (block, D): the
TPU lowering needs them tile-aligned or full.  Irrelevant kv blocks
(beyond the causal frontier or before the local window) are skipped with
``pl.when`` so a local-window pass does O(S*W) work, not O(S^2).

Oracle: ``repro.kernels.ref.mha`` (asserted in tests with interpret=True).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, softcap, q_offset, block_q, block_k,
            nk, kv_len):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = q_offset + iq * block_q
    k_lo = ik * block_k
    relevant = jnp.array(True)
    if causal:
        relevant = relevant & (k_lo <= q_lo + block_q - 1)
    if window and window > 0:
        relevant = relevant & (k_lo + block_k - 1 > q_lo - window)

    @pl.when(relevant)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                   # (bk, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq,bk)
        if softcap and softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
        mask = kpos < kv_len
        if causal:
            mask = mask & (kpos <= qpos)
        if window and window > 0:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                   # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
        m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale: Optional[float] = None, q_offset=0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q (B,S,H,D); k/v (B,T,K,D/Dv) with GQA H = g*K. Returns (B,S,H,Dv)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = H // K
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, S)
    bk = min(block_k, T)
    nq = -(-S // bq)
    nk = -(-T // bk)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=bq, block_k=bk, nk=nk, kv_len=T)

    heads_first = lambda a: jnp.swapaxes(a, 1, 2)      # (B,S,H,D)->(B,H,S,D)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)
