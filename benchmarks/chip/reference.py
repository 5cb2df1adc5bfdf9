"""Plain float32 reference of a decoder, and its fp8 control.

The layers and the head are the architecture's own, written from its
published description in ``families/<model_type>.py``; this module runs
them.  It imports nothing of the program: weights come from ``weights``
and the seed, one layer at a time, and every matrix product runs in
float32 at ``jax.default_matmul_precision("highest")``.

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
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import spec, weights

F8_MAX = 448.0


def _q8(x, axis=None):
    """Round to float8 e4m3 with a per-tensor (axis None) or per-row scale."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _same(a):
    return a


def _q8a(a):
    return _q8(a, -1)


@functools.lru_cache(maxsize=None)
def _programs(frozen: str, low: bool):
    """(embed, {stack key: one layer}, gap), jitted."""
    cfg = json.loads(frozen)
    fam = spec.family(cfg)
    q8w, q8a = (_q8, _q8a) if low else (_same, _same)

    def embed(e, tokens):
        return _f32(e)["tok_embed"][tokens]

    def stack(key):
        return lambda w, x: fam.layer(cfg, key, _f32(w), x, q8w, q8a)

    def gap(e, x, x_low, targets):
        """Per position: (gap of the served token, gap of fp8's first)."""
        e = _f32(e)
        ref = fam.logits(cfg, e, x, _same, _same)
        best = ref.max(-1)
        safe = jnp.maximum(targets, 0)
        served = best - jnp.take_along_axis(ref, safe[..., None], -1)[..., 0]
        if x_low is None:
            return served, served
        pick = fam.logits(cfg, e, x_low, _q8, _q8a).argmax(-1)
        low_gap = best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        return served, low_gap

    layers = {s.key: jax.jit(stack(s.key)) for s in fam.stacks(cfg)}
    return jax.jit(embed), layers, jax.jit(gap)


def _by_rows(layer, w, x, rows):
    """``layer`` over ``x`` in blocks of ``rows`` rows (None: all)."""
    if rows is None or rows >= x.shape[0]:
        return layer(w, x)
    return jnp.concatenate([layer(w, x[b:b + rows])
                            for b in range(0, x.shape[0], rows)])


def gaps(cfg: dict, seed: int, tokens: np.ndarray, targets: np.ndarray,
         control: bool = False, rows_per_block: int = 1):
    """``tokens`` (B, S) int, the sequences as sent and served, padded;
    ``targets``
    (B, S): at position p the token served after p, or -1.  Returns
    (served gaps, control gaps or None), each (B, S) with NaN where no
    token was served."""
    frozen = weights._freeze(cfg)
    with jax.default_matmul_precision("highest"):
        embed, layers, _ = _programs(frozen, False)
        low_layers = _programs(frozen, True)[1]
        gap = _programs(frozen, control)[2]
        rows = spec.family(cfg).reference_rows(cfg)
        e = weights.embed(cfg, seed)
        x = embed(e, jnp.asarray(tokens, jnp.int32))
        x_low = x if control else None
        for i, kind in enumerate(weights.kinds(cfg)):
            w = weights.layer(cfg, seed, i)
            x = _by_rows(layers[kind], w, x, rows)
            if control:
                x_low = _by_rows(low_layers[kind], w, x_low, rows)
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
