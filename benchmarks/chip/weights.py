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
import json
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import spec

NORM_SPREAD = 0.1


class Leaf(NamedTuple):
    """One tensor of a layer (under ``group``) or of the embedding (group
    None), uniform with standard deviation ``std`` (plus 1 for a norm
    scale).  A leaf with ``slices`` holds, along axis 0, the published
    indices it lists, each drawn from a key of its own: a share of a
    layer's experts then holds the same values as the uncut layer."""
    group: Optional[str]
    name: str
    shape: Tuple[int, ...]
    std: float
    is_norm: bool = False
    slices: Optional[Sequence[int]] = None


class Stack(NamedTuple):
    """``count`` layers of one kind under the parameter tree's ``key``."""
    key: str
    count: int
    leaves: Sequence[Leaf]


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


def _draw(key, leaf: Leaf):
    if leaf.slices is None:
        return _uniform(key, leaf.shape, leaf.std, leaf.is_norm)
    return jax.vmap(lambda e: _uniform(jax.random.fold_in(key, e),
                                       leaf.shape[1:], leaf.std,
                                       leaf.is_norm))(
        jnp.asarray(leaf.slices, jnp.uint32))


def _tree(key, leaves):
    """Leaf ``j`` from ``fold_in(key, j)``."""
    out = {}
    for j, leaf in enumerate(leaves):
        into = out if leaf.group is None else out.setdefault(leaf.group, {})
        into[leaf.name] = _draw(jax.random.fold_in(key, j), leaf)
    return out


def _layer(words, i, leaves):
    """Layer ``i`` of the model, whichever stack holds it."""
    key = jax.random.fold_in(jax.random.fold_in(_base(words), 1), i)
    return _tree(key, leaves)


def _freeze(cfg: dict) -> str:
    # the whole file, nested groups (such as a rope_scaling) included
    return json.dumps(cfg, sort_keys=True)


def layout(cfg: dict):
    """(stack, index in the model of its first layer), in model order."""
    out, first = [], 0
    for s in spec.family(cfg).stacks(cfg):
        out.append((s, first))
        first += s.count
    return out


def kinds(cfg: dict):
    """The stack key of every layer, in model order."""
    return [s.key for s, _ in layout(cfg) for _ in range(s.count)]


@functools.lru_cache(maxsize=None)
def _jitted(frozen: str):
    """(make, {stack key: one layer}, embed), jitted."""
    cfg = json.loads(frozen)
    stacks = layout(cfg)
    embed_leaves = spec.family(cfg).embed_leaves(cfg)

    def embed(words):
        return _tree(jax.random.fold_in(_base(words), 0), embed_leaves)

    def stack(leaves):
        return lambda words, i: _layer(words, i, leaves)

    def make(words):
        out = {"embed": embed(words)}
        for s, first in stacks:
            out[s.key] = jax.vmap(stack(s.leaves), (None, 0))(
                words, jnp.arange(first, first + s.count, dtype=jnp.uint32))
        return out

    layer = {s.key: jax.jit(stack(s.leaves)) for s, _ in stacks}
    return jax.jit(make), layer, jax.jit(embed)


def make(cfg: dict, seed: int, device=None):
    """The whole parameter tree, in one jitted call on ``device``."""
    fn = _jitted(_freeze(cfg))[0]
    words = seed_words(seed)
    if device is not None:
        words = jax.device_put(words, device)
    return fn(words)


def layer(cfg: dict, seed: int, i: int):
    """Layer ``i`` of the model alone, bit for bit as ``make`` holds it in
    its stack."""
    return _jitted(_freeze(cfg))[1][kinds(cfg)[i]](seed_words(seed),
                                                   jnp.uint32(i))


def embed(cfg: dict, seed: int):
    return _jitted(_freeze(cfg))[2](seed_words(seed))
