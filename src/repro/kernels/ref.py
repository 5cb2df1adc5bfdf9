"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *semantics* — each Pallas kernel in ``flash_attention.py`` /
``decode_attention.py`` / ``ssd_scan.py`` / ``rglru_scan.py`` must match the
corresponding function here (asserted in ``tests/test_kernels.py``).  The
model zoo calls them through ``repro.kernels.ops`` which dispatches between
this reference path (CPU / dry-run) and the Pallas path (TPU target).

Shape conventions:
  B batch, S query seq, T key seq, H query heads, K kv heads, D head dim,
  P ssd head dim, G ssd groups, N ssd state dim, W lru width.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(logits, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(logits / cap) * cap
    return logits


# ---------------------------------------------------------------------------
# Multi-head attention (train / prefill): causal, local-window, bidirectional
# ---------------------------------------------------------------------------

def mha(
    q: jax.Array,              # (B, S, H, D)
    k: jax.Array,              # (B, T, K, D)
    v: jax.Array,              # (B, T, K, Dv)
    *,
    causal: bool = True,
    window: int = 0,           # >0: local attention (last `window` keys)
    softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,         # absolute position of q[0] (chunked prefill)
    q_chunk: int = 0,          # >0: process queries in blocks of this size
    unroll: bool = False,      # unroll the q-block loop (exact HLO cost)
) -> jax.Array:
    B, S, H, D = q.shape
    if q_chunk and 0 < q_chunk < S and S % q_chunk == 0:
        nq = S // q_chunk
        qb = q.reshape(B, nq, q_chunk, H, D)

        if unroll:
            outs = [_mha_dense(qb[:, i], k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               q_offset=q_offset + i * q_chunk)
                    for i in range(nq)]
            return jnp.concatenate(outs, axis=1)

        def body2(_, xs):
            i, qi = xs
            o = _mha_dense_dyn(qi, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               q_offset_dyn=q_offset + i * q_chunk)
            return None, o
        idx = jnp.arange(nq)
        _, outs = jax.lax.scan(body2, None, (idx, jnp.moveaxis(qb, 1, 0)))
        return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, v.shape[-1])
    return _mha_dense(q, k, v, causal=causal, window=window, softcap=softcap,
                      scale=scale, q_offset=q_offset)


def _mha_dense(q, k, v, *, causal, window, softcap, scale, q_offset):
    B, S, H, D = q.shape
    qpos = jnp.arange(S)[:, None] + q_offset                # (S,1)
    return _mha_core(q, k, v, qpos, causal=causal, window=window,
                     softcap=softcap, scale=scale)


def _mha_dense_dyn(q, k, v, *, causal, window, softcap, scale, q_offset_dyn):
    S = q.shape[1]
    qpos = jnp.arange(S)[:, None] + q_offset_dyn
    return _mha_core(q, k, v, qpos, causal=causal, window=window,
                     softcap=softcap, scale=scale)


def _mha_core(q, k, v, qpos, *, causal, window, softcap, scale):
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    g = H // K
    scale = scale if scale is not None else D ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # GQA: group query heads over kv heads.
    qf = qf.reshape(B, S, K, g, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qf, kf)        # (B,K,g,S,T)
    logits = _softcap(logits, softcap)

    kpos = jnp.arange(T)[None, :]                           # (1,T)
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window and window > 0:
        mask = mask & (kpos > qpos - window)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, vf)
    return out.reshape(B, S, H, vf.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention: one query token against a (possibly partial) KV cache
# ---------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,              # (B, H, D)
    k_cache: jax.Array,        # (B, Smax, K, D), or (L, B, Smax, K, D)
    v_cache: jax.Array,        # (B, Smax, K, D), or (L, B, Smax, K, D)
    lengths: jax.Array,        # (B,) int32 — valid cache entries per row
    *,
    layer: Optional[jax.Array] = None,   # the stack's layer to read
    softcap: float = 0.0,
    scale: Optional[float] = None,
    window: int = 0,
) -> jax.Array:
    if layer is not None:
        k_cache, v_cache = k_cache[layer], v_cache[layer]
    B, H, D = q.shape
    Smax, K = k_cache.shape[1], k_cache.shape[2]
    g = H // K
    scale = scale if scale is not None else D ** -0.5

    qf = (q.astype(jnp.float32) * scale).reshape(B, K, g, D)
    logits = jnp.einsum("bkgd,btkd->bkgt", qf, k_cache.astype(jnp.float32))
    logits = _softcap(logits, softcap)
    pos = jnp.arange(Smax)[None]                            # (1,Smax)
    mask = pos < lengths[:, None]
    if window and window > 0:
        mask &= pos >= (lengths[:, None] - window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality) — chunked algorithm
# ---------------------------------------------------------------------------

def ssd(
    x: jax.Array,              # (B, S, H, P)
    dt: jax.Array,             # (B, S, H)  — already softplus'd, > 0
    A: jax.Array,              # (H,)       — negative
    Bm: jax.Array,             # (B, S, G, N)
    Cm: jax.Array,             # (B, S, G, N)
    D: Optional[jax.Array] = None,   # (H,) skip connection
    *,
    chunk: int = 256,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
    unroll: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S_in, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0
    hpg = H // G
    L = min(chunk, S_in)
    if S_in % L:
        # pad with dt=0 steps: decay exp(0)=1, zero input — exact no-ops
        pad = L - S_in % L
        z = lambda a: jnp.pad(a, [(0, 0), (0, pad)] +
                              [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = z(x), z(dt), z(Bm), z(Cm)
    S = x.shape[1]
    nc = S // L

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    # expand groups to heads once
    Bh = Bm.astype(jnp.float32)
    Ch = Cm.astype(jnp.float32)
    if G != H:
        Bh = jnp.repeat(Bh, hpg, axis=2)
        Ch = jnp.repeat(Ch, hpg, axis=2)

    # chunked views (chunk axis first for the scan)
    xc = jnp.moveaxis(xf.reshape(Bsz, nc, L, H, P), 1, 0)
    dtc = jnp.moveaxis(dtf.reshape(Bsz, nc, L, H), 1, 0)
    Bc = jnp.moveaxis(Bh.reshape(Bsz, nc, L, H, N), 1, 0)
    Cc = jnp.moveaxis(Ch.reshape(Bsz, nc, L, H, N), 1, 0)

    tri = jnp.tril(jnp.ones((L, L), dtype=bool))
    h0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def one_chunk(h, inp):
        xi, dti, Bi, Ci = inp            # (B,L,H,P),(B,L,H),(B,L,H,N)x2
        dA = dti * Af[None, None, :]                        # (B,L,H) <= 0
        cum = jnp.cumsum(dA, axis=1)                        # inclusive
        # intra-chunk: decay(i,j) = exp(cum_i - cum_j), j <= i
        decay = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])
        decay = jnp.where(tri[None, :, :, None], decay, 0.0)
        cb = jnp.einsum("bihn,bjhn->bijh", Ci, Bi)          # (B,i,j,H)
        w = cb * decay * dti[:, None, :, :]
        y_intra = jnp.einsum("bijh,bjhp->bihp", w, xi)
        # inter-chunk contribution: C_i . exp(cum_i) h_prev
        y_inter = jnp.einsum("bihn,bih,bhpn->bihp", Ci, jnp.exp(cum), h)
        # chunk-final state update
        last = cum[:, -1:, :]                               # (B,1,H)
        sdecay = jnp.exp(last - cum) * dti                  # (B,L,H)
        states = jnp.einsum("blh,blhn,blhp->bhpn", sdecay, Bi, xi)
        h_new = h * jnp.exp(last[:, 0])[:, :, None, None] + states
        return h_new, y_intra + y_inter

    if unroll:
        h = h0
        ys = []
        for c in range(nc):
            h, y = one_chunk(h, (xc[c], dtc[c], Bc[c], Cc[c]))
            ys.append(y)
        final = h
        yall = jnp.stack(ys, axis=0)
    else:
        final, yall = jax.lax.scan(one_chunk, h0, (xc, dtc, Bc, Cc))

    y = jnp.moveaxis(yall, 0, 1).reshape(Bsz, S, H, P)
    if D is not None:
        y = y + xf * D.astype(jnp.float32)[None, None, :, None]
    return y[:, :S_in].astype(x.dtype), final


def ssd_decode(
    x: jax.Array,              # (B, H, P)
    dt: jax.Array,             # (B, H)
    A: jax.Array,              # (H,)
    Bm: jax.Array,             # (B, G, N)
    Cm: jax.Array,             # (B, G, N)
    D: Optional[jax.Array],
    state: jax.Array,          # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """One recurrent SSD step. Returns (y (B,H,P), new_state)."""
    B, H, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    hpg = H // G
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bh = jnp.repeat(Bm, hpg, axis=1).astype(jnp.float32)    # (B,H,N)
    Ch = jnp.repeat(Cm, hpg, axis=1).astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32)[None])         # (B,H)
    upd = jnp.einsum("bh,bhn,bhp->bhpn", dtf, Bh, xf)
    new_state = state.astype(jnp.float32) * dA[:, :, None, None] + upd
    y = jnp.einsum("bhn,bhpn->bhp", Ch, new_state)
    if D is not None:
        y = y + xf * D.astype(jnp.float32)[None, :, None]
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence (Griffin / recurrentgemma)
# ---------------------------------------------------------------------------

def rglru(
    a: jax.Array,              # (B, S, W) — per-step decay in (0,1)
    b: jax.Array,              # (B, S, W) — per-step input term
    h0: Optional[jax.Array] = None,   # (B, W)
) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + b_t via associative scan.

    Returns (h (B,S,W), h_final (B,W)).
    """
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    if h0 is not None:
        # fold h0 into the first input term
        bf = bf.at[:, 0].add(af[:, 0] * h0.astype(jnp.float32))

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    ascan, bscan = jax.lax.associative_scan(combine, (af, bf), axis=1)
    return bscan.astype(a.dtype), bscan[:, -1]


def rglru_decode(a, b, h):
    """One step: a,b,h all (B, W)."""
    hf = (a.astype(jnp.float32) * h.astype(jnp.float32)
          + b.astype(jnp.float32))
    return hf.astype(a.dtype), hf
