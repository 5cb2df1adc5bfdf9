"""Plain float32 reference of a dense decoder, and its fp8 control.

Written from the published description of the llama architecture
(RMSNorm, rotary embedding on split halves, grouped-query causal attention
scaled by head_dim ** -0.5, SwiGLU MLP, tied or untied head).  It imports
nothing of
the program: weights come from ``weights`` and the seed, one layer at a
time, and every matrix product runs in float32 at
``jax.default_matmul_precision("highest")``.

``gaps`` runs the reference over whole sessions as sent and served
(padded to one length, so one compile serves every run) and returns, for
every position
that has a served token, how far that token's logit lies below the
reference's best.  With ``control=True`` it also runs the same forward
with every matrix operand rounded to float8 (e4m3, scaled per tensor for
weights and per row for activations) and returns the gap, under the
float32 logits, of the token that fp8 puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

F8_MAX = 448.0


def _q8(x, axis=None):
    """Round to float8 e4m3 with a per-tensor (axis None) or per-row scale."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs           # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, w, x, low):
    q8w = (lambda a: _q8(a)) if low else (lambda a: a)
    q8a = (lambda a: _q8(a, -1)) if low else (lambda a: a)
    eps = cfg["rms_norm_eps"]
    B, S, _ = x.shape
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    m, p = w["mixer"], w["mlp"]
    h = q8a(_rmsnorm(x, m["ln"], eps))
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, q8w(m["wq"])), pos,
              cfg["rope_theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, q8w(m["wk"])), pos,
              cfg["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", h, q8w(m["wv"]))
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    if low:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["head_dim"] ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + jnp.einsum("bqhd,hdm->bqm", q8a(o.reshape(B, S, -1)).reshape(
        o.shape), q8w(m["wo"]))
    h = q8a(_rmsnorm(x, p["ln"], eps))
    a = q8a(jax.nn.silu(h @ q8w(p["wi_gate"])) * (h @ q8w(p["wi_up"])))
    return x + a @ q8w(p["wo_mlp"])


def _logits(cfg, e, x, low):
    h = _rmsnorm(x, e["final_norm"], cfg["rms_norm_eps"])
    w = e["tok_embed"].T if cfg["tie_word_embeddings"] else e["unembed"]
    if low:
        h, w = _q8(h, -1), _q8(w)
    return h @ w


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.lru_cache(maxsize=None)
def _programs(frozen: tuple, low: bool):
    cfg = dict(frozen)

    def embed(e, tokens):
        return _f32(e)["tok_embed"][tokens]

    def layer(w, x):
        return _layer(cfg, _f32(w), x, low)

    def gap(e, x, x_low, targets):
        """Per position: (gap of the served token, gap of fp8's first)."""
        e = _f32(e)
        ref = _logits(cfg, e, x, False)
        best = ref.max(-1)
        safe = jnp.maximum(targets, 0)
        served = best - jnp.take_along_axis(ref, safe[..., None], -1)[..., 0]
        if x_low is None:
            return served, served
        pick = _logits(cfg, e, x_low, True).argmax(-1)
        low_gap = best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        return served, low_gap

    return jax.jit(embed), jax.jit(layer), jax.jit(gap)


def gaps(cfg: dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
         control: bool = False, rows_per_block: int = 1):
    """``tokens`` (B, S) int, the sequences as sent and served, padded;
    ``targets``
    (B, S): at position p the token served after p, or -1.  Returns
    (served gaps, control gaps or None), each (B, S) with NaN where no
    token was served."""
    frozen = weights._freeze(cfg)
    with jax.default_matmul_precision("highest"):
        embed, layer, _ = _programs(frozen, False)
        low_layer = _programs(frozen, True)[1]
        gap = _programs(frozen, control)[2]
        e = weights.embed(cfg, seed)
        x = embed(e, jnp.asarray(tokens, jnp.int32))
        x_low = x if control else None
        for i in range(cfg["num_hidden_layers"]):
            w = weights.layer(cfg, seed, i)
            x = layer(w, x)
            if control:
                x_low = low_layer(w, x_low)
            del w
        served, low = [], []
        for b in range(0, tokens.shape[0], rows_per_block):
            sl = slice(b, b + rows_per_block)
            t = jnp.asarray(targets[sl], jnp.int32)
            s_gap, l_gap = gap(e, x[sl], None if x_low is None
                               else x_low[sl], t)
            served.append(np.asarray(s_gap))
            low.append(np.asarray(l_gap))
    mask = targets < 0
    served = np.where(mask, np.nan, np.concatenate(served))
    low = np.where(mask, np.nan, np.concatenate(low)) if control else None
    return served, low
