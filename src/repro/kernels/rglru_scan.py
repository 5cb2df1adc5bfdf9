"""RG-LRU linear recurrence h_t = a_t * h_{t-1} + b_t for TPU.

Grid (batch, width_blocks, seq_blocks): the width dimension tiles across
VMEM lanes (block_w multiples of 128), the sequence dimension is innermost
and sequential with the (1, block_w) hidden state carried in VMEM scratch.
Inside a sequence block the recurrence steps with a ``fori_loop`` over
16-row slabs of time — elementwise VPU work, which is what this op is on
TPU (no MXU contraction exists in a diagonal RNN).

Oracle: ``repro.kernels.ref.rglru``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, hf_ref, carry_ref, *, bs, slab,
            ns, use_h0):
    js = pl.program_id(2)

    @pl.when(js == 0)
    def _init():
        if use_h0:
            carry_ref[...] = h0_ref[0].astype(jnp.float32)
        else:
            carry_ref[...] = jnp.zeros_like(carry_ref)

    # Rows are loaded and stored `slab` at a time: the TPU lowering needs
    # dynamic sublane offsets that are provably tile multiples.  Inside a
    # slab the recurrence steps row by row on values.
    rows = jax.lax.broadcasted_iota(jnp.int32, (slab, carry_ref.shape[1]), 0)

    def body(i, h):
        t0 = pl.multiple_of(i * slab, slab)
        a = a_ref[0, pl.ds(t0, slab), :].astype(jnp.float32)   # (slab, bw)
        b = b_ref[0, pl.ds(t0, slab), :].astype(jnp.float32)
        out = jnp.zeros_like(a)
        for r in range(slab):
            h = a[r:r + 1] * h + b[r:r + 1]                     # (1, bw)
            out = jnp.where(rows == r, h, out)
        o_ref[0, pl.ds(t0, slab), :] = out.astype(o_ref.dtype)
        return h

    carry_ref[...] = jax.lax.fori_loop(0, bs // slab, body, carry_ref[...])

    @pl.when(js == ns - 1)
    def _fin():
        hf_ref[0] = carry_ref[...].astype(hf_ref.dtype)


def rglru_scan(a, b, h0=None, *, block_s: int = 256, block_w: int = 512,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """a/b (B,S,W), h0 (B,W) or None. Returns (h (B,S,W), h_final (B,W))."""
    B, S, W = a.shape
    bs = min(block_s, S)
    bw = min(block_w, W)
    assert S % bs == 0 and W % bw == 0, (S, W, bs, bw)
    ns, nw = S // bs, W // bw
    use_h0 = h0 is not None
    h0_in = h0 if use_h0 else jnp.zeros((B, W), a.dtype)
    kernel = functools.partial(_kernel, bs=bs, slab=math.gcd(bs, 16), ns=ns,
                               use_h0=use_h0)

    # (B, 1, W) views of the per-row state: a (1, bw) tile of (B, W) would
    # put a 1 in the second-minor dim, which the TPU lowering refuses
    h, hf = pl.pallas_call(
        kernel,
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda b_, w, s: (b_, s, w)),
            pl.BlockSpec((1, bs, bw), lambda b_, w, s: (b_, s, w)),
            pl.BlockSpec((1, 1, bw), lambda b_, w, s: (b_, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bw), lambda b_, w, s: (b_, s, w)),
            pl.BlockSpec((1, 1, bw), lambda b_, w, s: (b_, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), a.dtype),
            jax.ShapeDtypeStruct((B, 1, W), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(a, b, h0_in[:, None])
    return h, hf[:, 0]
