"""Shared building blocks: norms, rope, embeddings, GQA attention, MLPs.

Every block exposes three entry points used by ``models.model``:
  * ``*_train``   — full-sequence forward, no cache.
  * ``*_prefill`` — full-sequence forward that also emits the decode cache.
  * ``*_decode``  — single-token forward against a cache (serve_step).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.distributed import constraints as cst
from .common import ModelConfig, ParamFactory, scaled_init, zeros_init, ones_init

Params = Dict[str, Any]


def shard_attn_q(cfg: ModelConfig, q: jax.Array) -> jax.Array:
    """Context parallelism for archs whose head count doesn't divide TP
    (qwen/llama4: 40 heads, TP 16): shard the q-sequence over 'model'
    instead of replicating the whole attention across it (16x flop waste
    observed in the baseline sweep — EXPERIMENTS.md §Perf)."""
    if not cfg.attn_seq_shard:
        return q
    mesh = cst.get_mesh()
    if mesh is None or q.ndim != 4:
        return q
    tp = mesh.shape.get("model", 1)
    if q.shape[2] % tp == 0:            # heads shard fine; nothing to do
        return cst.constrain(q, "dp", None, "tp", None)
    return cst.constrain(q, "dp", "tp", None, None)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(pf: ParamFactory, name: str, dim: int):
    pf.param(name, (dim,), ("norm",), init=ones_init)


def rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) or (..., H, D) with positions (..., S) or (...,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def init_embedding(pf: ParamFactory, cfg: ModelConfig):
    pf.param("tok_embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
             init=scaled_init, fan_in=cfg.d_model)
    if not cfg.tie_embeddings:
        pf.param("unembed", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                 init=scaled_init, fan_in=cfg.d_model)
    init_rmsnorm(pf, "final_norm", cfg.d_model)


def embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        emb = jnp.take(params["tok_embed"], tokens, axis=0)
        return emb.astype(cfg.compute_dtype)


def unembed(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("unembed"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("...d,vd->...v", x, params["tok_embed"])
        else:
            logits = jnp.einsum("...d,dv->...v", x, params["unembed"])
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
        return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def init_attention(pf: ParamFactory, cfg: ModelConfig, window: int = 0):
    d, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    init_rmsnorm(pf, "ln", d)
    pf.param("wq", (d, H, Dh), ("embed", "heads", "head_dim"), fan_in=d)
    pf.param("wk", (d, K, Dh), ("embed", "kv_heads", "head_dim"), fan_in=d)
    pf.param("wv", (d, K, Dh), ("embed", "kv_heads", "head_dim"), fan_in=d)
    pf.param("wo", (H, Dh, d), ("heads", "head_dim", "embed"), fan_in=H * Dh)
    if cfg.qkv_bias:
        pf.param("bq", (H, Dh), ("heads", "head_dim"), init=zeros_init)
        pf.param("bk", (K, Dh), ("kv_heads", "head_dim"), init=zeros_init)
        pf.param("bv", (K, Dh), ("kv_heads", "head_dim"), init=zeros_init)


def _qkv(p: Params, cfg: ModelConfig, x: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cfg.compute_dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cfg.compute_dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cfg.compute_dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cfg.compute_dtype)
        k = k + p["bk"].astype(cfg.compute_dtype)
        v = v + p["bv"].astype(cfg.compute_dtype)
    return q, k, v


def attention_train(p: Params, cfg: ModelConfig, x: jax.Array,
                    window: int = 0, causal: Optional[bool] = None) -> jax.Array:
    B, S, _ = x.shape
    h = rmsnorm(p["ln"], x, cfg.norm_eps)
    q, k, v = _qkv(p, cfg, h)
    pos = jnp.arange(S)[None]
    q = rope(q, jnp.broadcast_to(pos, (B, S)), cfg.rope_theta)
    k = rope(k, jnp.broadcast_to(pos, (B, S)), cfg.rope_theta)
    q = shard_attn_q(cfg, q)
    causal = cfg.is_causal if causal is None else causal
    o = ops.mha(q, k, v, causal=causal, window=window,
                q_chunk=cfg.attn_chunk, unroll=cfg.unroll_inner)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.compute_dtype))
    return x + out


def attention_prefill(p: Params, cfg: ModelConfig, x: jax.Array,
                      window: int = 0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    B, S, _ = x.shape
    with jax.named_scope("qkv"):
        h = rmsnorm(p["ln"], x, cfg.norm_eps)
        q, k, v = _qkv(p, cfg, h)
        pos = jnp.arange(S)[None]
        q = rope(q, jnp.broadcast_to(pos, (B, S)), cfg.rope_theta)
        k = rope(k, jnp.broadcast_to(pos, (B, S)), cfg.rope_theta)
        q = shard_attn_q(cfg, q)
    with jax.named_scope("attn_kernel"):
        o = ops.mha(q, k, v, causal=True, window=window,
                    q_chunk=cfg.attn_chunk, unroll=cfg.unroll_inner)
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bshk,hkd->bsd", o,
                         p["wo"].astype(cfg.compute_dtype))
        x = x + out
    with jax.named_scope("kv_write"):
        cache = {"k": k, "v": v}
    return x, cache


def attention_decode(p: Params, cfg: ModelConfig, x: jax.Array,
                     cache: Dict[str, jax.Array], lengths: jax.Array,
                     layer: Optional[jax.Array] = None,
                     window: int = 0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, d) one token per row; cache k/v: (B, Smax, K, Dh), or the
    model's layer stack (L, B, Smax, K, Dh) and this block's ``layer``.

    With the stack, the new position is written into it in place and the
    kernel reads the layer's blocks from it: no buffer holds one layer's
    cache apart from the stack."""
    B, _ = x.shape
    with jax.named_scope("qkv"):
        h = rmsnorm(p["ln"], x[:, None, :], cfg.norm_eps)
        q, k, v = _qkv(p, cfg, h)                   # (B,1,H,Dh)/(B,1,K,Dh)
        q = rope(q, lengths[:, None], cfg.rope_theta)[:, 0]  # (B,H,Dh)
        k = rope(k, lengths[:, None], cfg.rope_theta)[:, 0]  # (B,K,Dh)
        v = v[:, 0]
    with jax.named_scope("kv_write"):
        at = (jnp.arange(B), lengths)
        if layer is not None:
            at = (layer,) + at
        k_cache = cache["k"].at[at].set(k.astype(cache["k"].dtype))
        v_cache = cache["v"].at[at].set(v.astype(cache["v"].dtype))
    with jax.named_scope("attn_kernel"):
        o = ops.decode_attention(q, k_cache, v_cache, lengths + 1,
                                 layer=layer, window=window)
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(cfg.compute_dtype))
        x = x + out
    return x, {"k": k_cache, "v": v_cache}


def attention_cache_spec(cfg: ModelConfig, batch: int, max_seq: int,
                         window: int = 0) -> Dict[str, jax.ShapeDtypeStruct]:
    K, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    seq = min(max_seq, window) if window else max_seq
    shp = (batch, seq, K, Dh)
    return {"k": jax.ShapeDtypeStruct(shp, cfg.compute_dtype),
            "v": jax.ShapeDtypeStruct(shp, cfg.compute_dtype)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(pf: ParamFactory, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    init_rmsnorm(pf, "ln", d)
    if cfg.mlp_variant in ("swiglu", "geglu"):
        pf.param("wi_gate", (d, f), ("embed", "mlp"), fan_in=d)
        pf.param("wi_up", (d, f), ("embed", "mlp"), fan_in=d)
    else:
        pf.param("wi", (d, f), ("embed", "mlp"), fan_in=d)
    pf.param("wo_mlp", (f, d), ("mlp", "embed"), fan_in=f)


def mlp_core(p: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    """MLP without the residual/norm (shared by dense + MoE experts)."""
    cd = cfg.compute_dtype
    if cfg.mlp_variant == "swiglu":
        g = jax.nn.silu(h @ p["wi_gate"].astype(cd)) * (h @ p["wi_up"].astype(cd))
    elif cfg.mlp_variant == "geglu":
        g = jax.nn.gelu(h @ p["wi_gate"].astype(cd)) * (h @ p["wi_up"].astype(cd))
    elif cfg.mlp_variant == "relu2":
        g = jnp.square(jax.nn.relu(h @ p["wi"].astype(cd)))
    else:  # gelu
        g = jax.nn.gelu(h @ p["wi"].astype(cd))
    return g @ p["wo_mlp"].astype(cd)


def mlp_block(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    h = rmsnorm(p["ln"], x, cfg.norm_eps)
    return x + mlp_core(p, cfg, h)
