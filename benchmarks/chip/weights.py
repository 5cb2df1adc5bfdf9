"""Seeded weights, made on the device in the program's parameter layout.

The benchmark owns the weights: the program is handed them, and the
reference makes the same values again from the seed, one layer at a time,
without taking anything from the program.  Each tensor is uniform with the
standard deviation of a 1/sqrt(fan_in) init, built from integer random
bits by one exact conversion and one multiply, so any program that makes a
tensor gets the same bits.  Norm scales are 1 plus a uniform term, so that a norm that
drops its scale shows (standard deviation 0.1).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NORM_SPREAD = 0.1


def _layer_leaves(cfg: dict):
    """(group, name, shape, std, is_norm) of one decoder layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    return (("mixer", "ln", (d,), NORM_SPREAD, True),
            ("mixer", "wq", (d, h, dh), d ** -0.5, False),
            ("mixer", "wk", (d, k, dh), d ** -0.5, False),
            ("mixer", "wv", (d, k, dh), d ** -0.5, False),
            ("mixer", "wo", (h, dh, d), (h * dh) ** -0.5, False),
            ("mlp", "ln", (d,), NORM_SPREAD, True),
            ("mlp", "wi_gate", (d, f), d ** -0.5, False),
            ("mlp", "wi_up", (d, f), d ** -0.5, False),
            ("mlp", "wo_mlp", (f, d), f ** -0.5, False))


def _embed_leaves(cfg: dict):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = [("tok_embed", (v, d), d ** -0.5, False)]
    if not cfg["tie_word_embeddings"]:
        out.append(("unembed", (d, v), d ** -0.5, False))
    out.append(("final_norm", (d,), NORM_SPREAD, True))
    return out


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of up to 64 bits as two uint32 words (traced, so one compile
    serves every seed)."""
    return jnp.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     jnp.uint32)


def _base(words):
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def _uniform(key, shape, std, is_norm):
    bits = jax.random.bits(key, shape, jnp.uint32)
    ints = (bits >> 8).astype(jnp.int32) - (1 << 23)     # [-2^23, 2^23)
    x = ints.astype(jnp.float32) * jnp.float32(std * math.sqrt(3) / 2 ** 23)
    if is_norm:
        x = x + 1.0
    return x.astype(jnp.bfloat16)


def _layer(cfg, words, i):
    key = jax.random.fold_in(jax.random.fold_in(_base(words), 1), i)
    out = {"mixer": {}, "mlp": {}}
    for j, (grp, name, shape, std, is_norm) in enumerate(_layer_leaves(cfg)):
        out[grp][name] = _uniform(jax.random.fold_in(key, j), shape, std,
                                  is_norm)
    return out


def _embed(cfg, words):
    key = jax.random.fold_in(_base(words), 0)
    return {name: _uniform(jax.random.fold_in(key, j), shape, std, is_norm)
            for j, (name, shape, std, is_norm) in
            enumerate(_embed_leaves(cfg))}


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.lru_cache(maxsize=None)
def _jitted(frozen: tuple):
    cfg = dict(frozen)
    n = cfg["num_hidden_layers"]

    def make(words):
        layers = jax.vmap(lambda i: _layer(cfg, words, i))(
            jnp.arange(n, dtype=jnp.uint32))
        return {"embed": _embed(cfg, words), "layers": layers}

    return (jax.jit(make),
            jax.jit(lambda words, i: _layer(cfg, words, i)),
            jax.jit(lambda words: _embed(cfg, words)))


def make(cfg: dict, seed: int, device=None):
    """The whole parameter tree, in one jitted call on ``device``."""
    fn = _jitted(_freeze(cfg))[0]
    words = seed_words(seed)
    if device is not None:
        words = jax.device_put(words, device)
    return fn(words)


def layer(cfg: dict, seed: int, i: int):
    """Layer ``i`` alone, bit for bit as ``make`` holds it."""
    return _jitted(_freeze(cfg))[1](seed_words(seed), jnp.uint32(i))


def embed(cfg: dict, seed: int):
    return _jitted(_freeze(cfg))[2](seed_words(seed))
