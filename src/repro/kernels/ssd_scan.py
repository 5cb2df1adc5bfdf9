"""Mamba-2 SSD chunked scan for TPU.

Grid (batch, heads, chunks) with the chunk dimension innermost/sequential;
the (P, N) recurrent state lives in VMEM scratch and carries across chunk
steps.  Per chunk: intra-chunk quadratic term (L x L decay-weighted C.B^T),
inter-chunk contribution from the carried state, and the state update —
all fp32 in VMEM, MXU-shaped matmuls (L, N, P multiples of 128 at
production sizes).

Oracle: ``repro.kernels.ref.ssd``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, st_ref,
            h_ref, *, L, has_d):
    ih = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)                      # (L, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)                # (1, L)
    A = a_ref[ih]                                            # scalar (SMEM)
    Bm = b_ref[0, 0].astype(jnp.float32)                     # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                     # (L, N)

    # Per-step vectors are needed both along lanes (row) and sublanes
    # (column); masked reductions over (L, L) move them between the two.
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = rows >= cols
    eye = rows == cols
    dA_row = dt_row * A                                      # (1, L) <= 0
    cum_col = jnp.sum(jnp.where(tri, dA_row, 0.0), axis=1,
                      keepdims=True)                         # (L, 1) inclusive
    cum_row = jnp.sum(jnp.where(eye, cum_col, 0.0), axis=0,
                      keepdims=True)                         # (1, L)
    dt_col = jnp.sum(jnp.where(eye, dt_row, 0.0), axis=1,
                     keepdims=True)                          # (L, 1)
    decay = jnp.exp(jnp.where(tri, cum_col - cum_row, -jnp.inf))  # (L, L)

    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (L, L)
    w = cb * decay * dt_row
    y = jax.lax.dot(w, x)                                    # intra (L, P)

    h = h_ref[...]                                           # (P, N)
    cexp = Cm * jnp.exp(cum_col)                             # (L, N)
    y = y + jax.lax.dot_general(cexp, h, (((1,), (1,)), ((), ())))

    last = jnp.sum(dA_row, axis=1, keepdims=True)            # (1, 1)
    sdecay = jnp.exp(last - cum_col) * dt_col                # (L, 1)
    upd = jax.lax.dot_general(x, Bm * sdecay,
                              (((0,), (0,)), ((), ())))      # (P, N)
    h_new = h * jnp.exp(last) + upd
    h_ref[...] = h_new

    if has_d:
        y = y + x * d_ref[ih]
    y_ref[0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = h_new                                     # last write wins


def ssd_scan(x, dt, A, Bm, Cm, D=None, *, chunk: int = 256,
             init_state=None, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,H,P); dt (B,S,H); A (H,); Bm/Cm (B,S,G,N); D (H,) or None."""
    assert init_state is None, "kernel path starts from zero state"
    B, S_in, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    L = min(chunk, S_in)
    if S_in % L:
        pad = L - S_in % L            # dt=0 pad steps are exact no-ops
        z = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                              + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = z(x), z(dt), z(Bm), z(Cm)
    B, S, H, P = x.shape
    nc = S // L
    has_d = D is not None
    d_in = (D if has_d else jnp.zeros((H,), jnp.float32))
    kernel = functools.partial(_kernel, L=L, has_d=has_d)
    # heads ahead of the sequence, so every tile's last two dims are
    # (chunk, feature) — tile-aligned or full, as the TPU lowering needs
    heads_first = lambda a: jnp.swapaxes(a, 1, 2)
    dt_rows = jnp.transpose(dt.astype(jnp.float32), (0, 2, 1))[:, :, None]

    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),           # A (H,)
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, h // hpg, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),           # D (H,)
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(heads_first(x), dt_rows, A.astype(jnp.float32), heads_first(Bm),
      heads_first(Cm), d_in.astype(jnp.float32))
    return heads_first(y)[:, :S_in], state
