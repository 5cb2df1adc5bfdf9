"""The llama architecture: a dense decoder, as in the published description
(RMSNorm, rotary embedding on split halves, grouped-query causal attention
scaled by head_dim ** -0.5, SwiGLU MLP, tied or untied head).

The program runs it as its ``dense`` family: one stack of layers under
``layers``, and a cache ``{"k", "v"}`` whose leaves are (layers, batch,
positions, kv heads, head_dim).  The interface is ``spec.family``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.weights import NORM_SPREAD, Leaf, Stack


def model_config(cfg: dict):
    """The program's ``ModelConfig``; the file's keys use the published
    config's names."""
    from repro.models.common import ModelConfig
    if cfg["hidden_act"] != "silu" or cfg["torch_dtype"] != "bfloat16":
        raise ValueError(f"{cfg['name']}: unsupported hidden_act or "
                         f"torch_dtype")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], mlp_variant="swiglu",
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)


# -- seeded weights -----------------------------------------------------------

def stacks(cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    leaves = (Leaf("mixer", "ln", (d,), NORM_SPREAD, True),
              Leaf("mixer", "wq", (d, h, dh), d ** -0.5),
              Leaf("mixer", "wk", (d, k, dh), d ** -0.5),
              Leaf("mixer", "wv", (d, k, dh), d ** -0.5),
              Leaf("mixer", "wo", (h, dh, d), (h * dh) ** -0.5),
              Leaf("mlp", "ln", (d,), NORM_SPREAD, True),
              Leaf("mlp", "wi_gate", (d, f), d ** -0.5),
              Leaf("mlp", "wi_up", (d, f), d ** -0.5),
              Leaf("mlp", "wo_mlp", (f, d), f ** -0.5))
    return [Stack("layers", cfg["num_hidden_layers"], leaves)]


def embed_leaves(cfg: dict):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = [Leaf(None, "tok_embed", (v, d), d ** -0.5)]
    if not cfg["tie_word_embeddings"]:
        out.append(Leaf(None, "unembed", (d, v), d ** -0.5))
    out.append(Leaf(None, "final_norm", (d,), NORM_SPREAD, True))
    return out


# -- the float32 reference ----------------------------------------------------

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs           # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(cfg, stack, w, x, q8w, q8a):
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
    q, k, v = q8a(q), q8a(k), q8a(v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["head_dim"] ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + jnp.einsum("bqhd,hdm->bqm", q8a(o.reshape(B, S, -1)).reshape(
        o.shape), q8w(m["wo"]))
    h = q8a(_rmsnorm(x, p["ln"], eps))
    a = q8a(jax.nn.silu(h @ q8w(p["wi_gate"])) * (h @ q8w(p["wi_up"])))
    return x + a @ q8w(p["wo_mlp"])


def logits(cfg, e, x, q8w, q8a):
    h = _rmsnorm(x, e["final_norm"], cfg["rms_norm_eps"])
    w = e["tok_embed"].T if cfg["tie_word_embeddings"] else e["unembed"]
    return q8a(h) @ q8w(w)


def reference_rows(cfg: dict):
    return None


# -- work counts --------------------------------------------------------------

def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    return d * h * dh + 2 * d * k * dh + h * dh * d + 3 * d * f


def matmul_flops(cfg: dict) -> float:
    return 2.0 * layer_matmul_params(cfg) * cfg["num_hidden_layers"]


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: dict, path: str) -> float:
    """QK^T and PV: 4 x head_dim a head, on either path."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"])


def cache_bytes(cfg: dict) -> int:
    """K and V of one position over every layer, bf16."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def prefill_bytes(cfg: dict) -> int:
    """A position's queries, keys and values read once and its output
    written, bf16, over every layer."""
    per = (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) \
        * cfg["head_dim"] * 2
    return cfg["num_hidden_layers"] * per
