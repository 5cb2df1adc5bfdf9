"""Single-token GQA decode attention against a (possibly ring) KV cache.

Grid (batch, kv_blocks): each step loads one (block_s, K, D) KV tile — all
K kv heads at once, so the tile's last two dims are the cache's full
(K, D) as the TPU lowering requires — into VMEM, and updates one
online-softmax accumulator per kv head for the g query heads sharing it.
The caches may be a model's whole layer stack (L, B, Smax, K, D): the
`layer` index and `lengths` ride in scalar prefetch, the K/V index maps
pick the layer's blocks, and they are DMA'd from where they lie in the
stack, so no per-layer copy of the cache exists.  `lengths` (scalar per
batch row) masks the tail block; a local `window` restricts attention to
the last W positions (ring caches pass window=0 and a clamped `lengths`).

Oracle: ``repro.kernels.ref.decode_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Scoped VMEM the kernel asks for (a v5e's default is 16 MiB, which
# 512-position K and V tiles of 32 x 128 bf16, double-buffered, fill on
# their own), and the share of it that those tiles may take; the rest holds
# q, the output, the accumulators and the body's f32 temporaries.
VMEM_LIMIT_BYTES = 32 * 2 ** 20
KV_VMEM_BYTES = 24 * 2 ** 20
MAX_BLOCK_S = 512


def kv_block(smax: int, kv_bytes_per_position: int) -> int:
    """The largest KV block, up to ``MAX_BLOCK_S`` positions, that divides
    ``smax`` (a multiple of 8 unless it is all of ``smax``) and whose K and
    V tiles, double-buffered, fit in ``KV_VMEM_BYTES``."""
    for bs in range(min(smax, MAX_BLOCK_S), 0, -1):
        if smax % bs or (bs % 8 and bs != smax):
            continue
        if 2 * bs * kv_bytes_per_position <= KV_VMEM_BYTES:
            return bs
    raise ValueError(f"no KV block of {smax} positions fits VMEM")


def _kernel(len_ref, _layer_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale, softcap, window, block_s, ns, n_kv, g):
    js = pl.program_id(1)

    @pl.when(js == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0)]
    s_lo = js * block_s
    relevant = s_lo < length
    if window and window > 0:
        relevant = relevant & (s_lo + block_s > length - window)

    @pl.when(relevant)
    def _update():
        pos = s_lo + jax.lax.broadcasted_iota(jnp.int32, (g, block_s), 1)
        mask = pos < length
        if window and window > 0:
            mask = mask & (pos >= length - window)
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32) * scale       # (g, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)         # (bs, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)         # (bs, Dv)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (g,bs)
            if softcap and softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                                 # (g, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            alpha = jnp.exp(m_prev - m_cur)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(p, v)
            m_ref[h] = m_cur

    @pl.when(js == ns - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, layer=None,
                     softcap=0.0, scale: Optional[float] = None, window=0,
                     block_s: Optional[int] = None, interpret: bool = False):
    """q (B,H,D); lengths (B,). Returns (B,H,Dv).

    Caches (B,Smax,K,D/Dv), or a layer stack (L,B,Smax,K,D/Dv) of which
    layer ``layer`` is read.  ``block_s`` defaults to ``kv_block``."""
    if layer is None:                       # a stack of one
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    B, H, D = q.shape
    Smax, K = k_cache.shape[2], k_cache.shape[3]
    Dv = v_cache.shape[-1]
    g = H // K
    scale = scale if scale is not None else D ** -0.5
    bs = block_s or kv_block(Smax, K * (D + Dv) * k_cache.dtype.itemsize)
    assert Smax % bs == 0, (Smax, bs)
    ns = Smax // bs

    qr = q.reshape(B, K, g, D)
    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               window=window, block_s=bs, ns=ns, n_kv=K, g=g)
    kv_map = lambda b, j, lens, li: (li[0], b, j, 0, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                # lengths, layer
            grid=(B, ns),
            in_specs=[
                pl.BlockSpec((1, K, g, D), lambda b, j, *_: (b, 0, 0, 0)),
                pl.BlockSpec((None, 1, bs, K, D), kv_map),
                pl.BlockSpec((None, 1, bs, K, Dv), kv_map),
            ],
            out_specs=pl.BlockSpec((1, K, g, Dv),
                                   lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, g, Dv), jnp.float32),
                pltpu.VMEM((K, g, 1), jnp.float32),
                pltpu.VMEM((K, g, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, K, g, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      qr, k_cache, v_cache)
    return out.reshape(B, H, Dv)
