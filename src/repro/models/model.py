"""Model assembly: config -> init / train / prefill / decode entry points.

All families share one ``Model`` facade:

  * params are a pytree with a stacked ``layers`` subtree (scan-over-layers;
    the hybrid family scans pattern *groups* + an unrolled tail),
  * every leaf has a logical-axes annotation (``param_axes``) consumed by
    ``repro.distributed.sharding_rules``,
  * ``decode_step`` implements serve_step: one token per sequence against the
    family-specific cache (KV / latent-KV / SSM state / LRU state).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constraints as cst
from .common import ModelConfig, ParamFactory, count_params, scaled_init
from . import layers, moe, mla, rglru, ssd

Params = Dict[str, Any]


def _sp(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Sequence-parallel residual: pin the (B,S,d) stream's S over 'model'
    between blocks, so norms/elementwise run sharded and GSPMD lowers the
    per-block boundary to all-gather + reduce-scatter (half the bytes of
    the default per-sublayer all-reduce pair)."""
    if cfg.seq_parallel_residual and x.ndim == 3:
        return cst.constrain(x, "dp", "tp", None)
    return x


# ---------------------------------------------------------------------------
# Per-kind block init/apply
# ---------------------------------------------------------------------------

def _init_block(pf: ParamFactory, cfg: ModelConfig, kind: str):
    if kind in ("attn", "wattn"):
        sub = pf.subtree("mixer")
        if cfg.mla and kind == "attn":
            mla.init_mla(sub, cfg)
        else:
            layers.init_attention(sub, cfg)
        if cfg.family == "moe" and kind == "attn":
            moe.init_moe_mlp(pf.subtree("mlp"), cfg)
        else:
            layers.init_mlp(pf.subtree("mlp"), cfg)
    elif kind == "rglru":
        rglru.init_rglru_block(pf.subtree("mixer"), cfg)
        layers.init_mlp(pf.subtree("mlp"), cfg)
    elif kind == "ssd":
        ssd.init_ssd_block(pf.subtree("mixer"), cfg)
    else:
        raise ValueError(kind)


def _block_train(bp: Params, cfg: ModelConfig, x: jax.Array, kind: str):
    if kind == "attn":
        if cfg.mla:
            x = mla.mla_train(bp["mixer"], cfg, x)
        else:
            x = layers.attention_train(bp["mixer"], cfg, x, window=0)
        if cfg.family == "moe":
            x = moe.moe_block(bp["mlp"], cfg, x)
        else:
            x = layers.mlp_block(bp["mlp"], cfg, x)
    elif kind == "rglru":
        x = rglru.rglru_train(bp["mixer"], cfg, x)
        x = layers.mlp_block(bp["mlp"], cfg, x)
    elif kind == "ssd":
        x = ssd.ssd_train(bp["mixer"], cfg, x)
    elif kind == "wattn":   # hybrid local-window attention
        x = layers.attention_train(bp["mixer"], cfg, x, window=cfg.attn_window)
        x = layers.mlp_block(bp["mlp"], cfg, x)
    return x


def _mixer_scope(cfg: ModelConfig, kind: str) -> str:
    """The named scope of a block's mixer (listed in PERF.md, Layers)."""
    if kind == "attn" and cfg.mla:
        return "mla"
    return "attn" if kind == "wattn" else kind


def _block_mlp(bp, cfg, x, kind):
    """The block's MLP (an SSD block has none), under its named scope."""
    routed = cfg.family == "moe" and kind == "attn"
    with jax.named_scope("moe" if routed else "mlp"):
        if routed:
            return moe.moe_block(bp["mlp"], cfg, x)
        return layers.mlp_block(bp["mlp"], cfg, x)


def _block_prefill(bp, cfg, x, kind):
    with jax.named_scope(_mixer_scope(cfg, kind)):
        if kind == "attn":
            if cfg.mla:
                x, cache = mla.mla_prefill(bp["mixer"], cfg, x)
            else:
                x, cache = layers.attention_prefill(bp["mixer"], cfg, x)
        elif kind == "rglru":
            x, cache = rglru.rglru_prefill(bp["mixer"], cfg, x)
        elif kind == "ssd":
            x, cache = ssd.ssd_prefill(bp["mixer"], cfg, x)
        elif kind == "wattn":
            x, cache = layers.attention_prefill(bp["mixer"], cfg, x)
            w = cfg.attn_window
            cache = {"k": cache["k"][:, -w:], "v": cache["v"][:, -w:]}
    if kind != "ssd":
        x = _block_mlp(bp, cfg, x, kind)
    return x, cache


def _block_decode(bp, cfg, x, cache, lengths, kind, layer=None):
    """``layer``: ``cache`` is the model's layer stack, read and written at
    that layer (``attn`` without MLA only)."""
    with jax.named_scope(_mixer_scope(cfg, kind)):
        if kind == "attn":
            if cfg.mla:
                x, cache = mla.mla_decode(bp["mixer"], cfg, x, cache, lengths)
            else:
                x, cache = layers.attention_decode(bp["mixer"], cfg, x, cache,
                                                   lengths, layer)
        elif kind == "rglru":
            x, cache = rglru.rglru_decode(bp["mixer"], cfg, x, cache,
                                          lengths)
        elif kind == "ssd":
            x, cache = ssd.ssd_decode(bp["mixer"], cfg, x, cache, lengths)
        elif kind == "wattn":
            ring_len = cache["k"].shape[1]
            slot = lengths % ring_len
            valid = jnp.minimum(lengths + 1, ring_len)
            x, cache = _ring_attention_decode(bp["mixer"], cfg, x, cache,
                                              lengths, slot, valid)
    if kind != "ssd":
        x = _block_mlp(bp, cfg, x[:, None, :], kind)[:, 0]
    return x, cache


def _ring_attention_decode(p, cfg, x, cache, lengths, slot, valid):
    """Window attention against a ring-buffer cache (slot = pos % window)."""
    from repro.kernels import ops
    B, _ = x.shape
    with jax.named_scope("qkv"):
        h = layers.rmsnorm(p["ln"], x[:, None, :], cfg.norm_eps)
        q, k, v = layers._qkv(p, cfg, h)
        q = layers.rope(q, lengths[:, None], cfg.rope_theta)[:, 0]
        k = layers.rope(k, lengths[:, None], cfg.rope_theta)[:, 0]
        v = v[:, 0]
    with jax.named_scope("kv_write"):
        bidx = jnp.arange(B)
        k_c = cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype))
        v_c = cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype))
    with jax.named_scope("attn_kernel"):
        o = ops.decode_attention(q, k_c, v_c, valid)
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(cfg.compute_dtype))
        x = x + out
    return x, {"k": k_c, "v": v_c}


def _block_cache_spec(cfg, kind, batch, max_seq):
    if kind == "attn":
        if cfg.mla:
            return mla.mla_cache_spec(cfg, batch, max_seq)
        return layers.attention_cache_spec(cfg, batch, max_seq)
    if kind == "rglru":
        return rglru.rglru_cache_spec(cfg, batch, max_seq)
    if kind == "ssd":
        return ssd.ssd_cache_spec(cfg, batch, max_seq)
    if kind == "wattn":
        return layers.attention_cache_spec(cfg, batch, max_seq,
                                           window=cfg.attn_window)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model facade
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._hybrid = bool(cfg.block_pattern) and len(set(cfg.block_pattern)) > 1
        if self._hybrid:
            period = len(cfg.block_pattern)
            self.n_groups = cfg.n_layers // period
            self.tail_kinds = tuple(
                self._kind(i) for i in range(self.n_groups * period,
                                             cfg.n_layers))
            self.group_kinds = tuple(self._kind(i) for i in range(period))
        self._axes: Optional[Any] = None

    def _kind(self, layer_idx: int) -> str:
        k = self.cfg.block_kind(layer_idx)
        if k == "attn" and self.cfg.attn_window:
            return "wattn"
        return k

    # -- init ---------------------------------------------------------------

    def _init_one_layer(self, rng, kind: str):
        pf = ParamFactory(rng, self.cfg.param_dtype)
        _init_block(pf, self.cfg, kind)
        return pf.params, pf.axes

    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        rngs = jax.random.split(rng, 4)
        pf = ParamFactory(rngs[0], cfg.param_dtype)
        layers.init_embedding(pf, cfg)
        params: Params = {"embed": pf.params}
        axes: Dict[str, Any] = {"embed": pf.axes}

        if cfg.frontend == "audio":
            fp = ParamFactory(rngs[2], cfg.param_dtype)
            fp.param("w_feat", (cfg.frontend_dim, cfg.d_model),
                     ("frontend", "embed"), fan_in=cfg.frontend_dim)
            params["frontend"] = fp.params
            axes["frontend"] = fp.axes
        elif cfg.frontend == "vision":
            fp = ParamFactory(rngs[2], cfg.param_dtype)
            fp.param("w_patch", (cfg.frontend_dim, cfg.d_model),
                     ("frontend", "embed"), fan_in=cfg.frontend_dim)
            params["frontend"] = fp.params
            axes["frontend"] = fp.axes

        if self._hybrid:
            def init_group(key):
                ps, axs = {}, {}
                keys = jax.random.split(key, len(self.group_kinds))
                for i, kind in enumerate(self.group_kinds):
                    ps[f"b{i}"], axs[f"b{i}"] = self._init_one_layer(keys[i],
                                                                     kind)
                return ps, axs
            gkeys = jax.random.split(rngs[1], self.n_groups)
            stacked, gaxes = jax.vmap(lambda k: init_group(k)[0])(gkeys), \
                init_group(gkeys[0])[1]
            params["groups"] = stacked
            axes["groups"] = jax.tree_util.tree_map(
                lambda a: ("layers",) + tuple(a), gaxes,
                is_leaf=_is_axes_leaf)
            tkeys = jax.random.split(rngs[3], max(len(self.tail_kinds), 1))
            params["tail"] = {}
            axes["tail"] = {}
            for i, kind in enumerate(self.tail_kinds):
                params["tail"][f"t{i}"], axes["tail"][f"t{i}"] = \
                    self._init_one_layer(tkeys[i], kind)
        else:
            kind = self._kind(0)
            lkeys = jax.random.split(rngs[1], cfg.n_layers)
            stacked = jax.vmap(lambda k: self._init_one_layer(k, kind)[0])(
                lkeys)
            _, laxes = self._init_one_layer(lkeys[0], kind)
            params["layers"] = stacked
            axes["layers"] = jax.tree_util.tree_map(
                lambda a: ("layers",) + tuple(a), laxes,
                is_leaf=_is_axes_leaf)
        self._axes = axes
        return params

    def param_axes(self) -> Any:
        if self._axes is None:
            jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return self._axes

    # -- embedding-side input handling ---------------------------------------

    def _embed_inputs(self, params: Params, batch: Dict[str, jax.Array]):
        cfg = self.cfg
        if cfg.frontend == "audio":
            x = (batch["features"].astype(cfg.compute_dtype)
                 @ params["frontend"]["w_feat"].astype(cfg.compute_dtype))
            return x
        x = layers.embed(params["embed"], cfg, batch["tokens"])
        if cfg.frontend == "vision" and "patches" in batch:
            proj = (batch["patches"].astype(cfg.compute_dtype)
                    @ params["frontend"]["w_patch"].astype(cfg.compute_dtype))
            x = x.at[:, :proj.shape[1]].set(proj)
        return x

    # -- layer-stack application ---------------------------------------------

    def _remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        if self.cfg.remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            return jax.checkpoint(fn, policy=policy)
        return jax.checkpoint(fn)   # "layer": save nothing

    def _apply_stack_train(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        if self._hybrid:
            def group_fn(x, gp):
                for i, kind in enumerate(self.group_kinds):
                    x = _block_train(gp[f"b{i}"], cfg, x, kind)
                return x, None
            if cfg.scan_layers:
                x, _ = jax.lax.scan(self._remat(group_fn), x,
                                    params["groups"])
            else:
                for g in range(self.n_groups):
                    gp = _tree_index(params["groups"], g)
                    x, _ = self._remat(group_fn)(x, gp)
            for i, kind in enumerate(self.tail_kinds):
                x = _block_train(params["tail"][f"t{i}"], cfg, x, kind)
            return x
        kind = self._kind(0)
        def body(x, lp):
            return _sp(cfg, _block_train(lp, cfg, _sp(cfg, x), kind)), None
        if cfg.scan_layers:
            x, _ = jax.lax.scan(self._remat(body), x, params["layers"])
        else:
            for li in range(cfg.n_layers):
                x, _ = self._remat(body)(x, _tree_index(params["layers"], li))
        return x

    def _apply_stack_prefill(self, params: Params, x: jax.Array):
        cfg = self.cfg
        if self._hybrid:
            caches: Dict[str, Any] = {}
            def group_fn(x, gp):
                cs = {}
                for i, kind in enumerate(self.group_kinds):
                    x, cs[f"b{i}"] = _block_prefill(gp[f"b{i}"], cfg, x, kind)
                return x, cs
            if cfg.scan_layers:
                x, gcaches = jax.lax.scan(group_fn, x, params["groups"])
            else:
                gc_list = []
                for g in range(self.n_groups):
                    x, gc = group_fn(x, _tree_index(params["groups"], g))
                    gc_list.append(gc)
                gcaches = _tree_stack(gc_list)
            caches["groups"] = gcaches
            caches["tail"] = {}
            for i, kind in enumerate(self.tail_kinds):
                x, caches["tail"][f"t{i}"] = _block_prefill(
                    params["tail"][f"t{i}"], cfg, x, kind)
        else:
            kind = self._kind(0)
            def body(x, lp):
                return _block_prefill(lp, cfg, x, kind)
            if cfg.scan_layers:
                x, caches = jax.lax.scan(body, x, params["layers"])
            else:
                c_list = []
                for li in range(cfg.n_layers):
                    x, c = body(x, _tree_index(params["layers"], li))
                    c_list.append(c)
                caches = _tree_stack(c_list)
        return x, caches

    def _apply_stack_decode(self, params: Params, cache: Any, x: jax.Array,
                            lengths: jax.Array):
        cfg = self.cfg
        if self._hybrid:
            def group_fn(x, xs):
                gp, gc = xs
                ncs = {}
                for i, kind in enumerate(self.group_kinds):
                    x, ncs[f"b{i}"] = _block_decode(gp[f"b{i}"], cfg, x,
                                                    gc[f"b{i}"], lengths, kind)
                return x, ncs
            if cfg.scan_layers:
                x, gcaches = jax.lax.scan(group_fn, x,
                                          (params["groups"], cache["groups"]))
            else:
                gc_list = []
                for g in range(self.n_groups):
                    x, gc = group_fn(x, (_tree_index(params["groups"], g),
                                         _tree_index(cache["groups"], g)))
                    gc_list.append(gc)
                gcaches = _tree_stack(gc_list)
            new_cache = {"groups": gcaches, "tail": {}}
            for i, kind in enumerate(self.tail_kinds):
                x, new_cache["tail"][f"t{i}"] = _block_decode(
                    params["tail"][f"t{i}"], cfg, x, cache["tail"][f"t{i}"],
                    lengths, kind)
        elif self._kind(0) == "attn" and not cfg.mla:
            # the stacked cache rides in the carry and each block updates
            # its one position in place: no layer's cache is sliced out
            # of the stack and written back
            def body(carry, xs):
                x, cache = carry
                lp, li = xs
                return _block_decode(lp, cfg, x, cache, lengths, "attn",
                                     layer=li), None
            if cfg.scan_layers:
                (x, new_cache), _ = jax.lax.scan(
                    body, (x, cache),
                    (params["layers"], jnp.arange(cfg.n_layers)))
            else:
                new_cache = cache
                for li in range(cfg.n_layers):
                    (x, new_cache), _ = body(
                        (x, new_cache), (_tree_index(params["layers"], li),
                                         li))
        else:
            kind = self._kind(0)
            def body(x, xs):
                lp, lc = xs
                return _block_decode(lp, cfg, x, lc, lengths, kind)
            if cfg.scan_layers:
                x, new_cache = jax.lax.scan(body, x,
                                            (params["layers"], cache))
            else:
                c_list = []
                for li in range(cfg.n_layers):
                    x, c = body(x, (_tree_index(params["layers"], li),
                                    _tree_index(cache, li)))
                    c_list.append(c)
                new_cache = _tree_stack(c_list)
        return x, new_cache

    # -- public entry points --------------------------------------------------

    def forward_train(self, params: Params, batch: Dict[str, jax.Array]):
        x = self._embed_inputs(params, batch)
        x = self._apply_stack_train(params, x)
        return layers.unembed(params["embed"], self.cfg, x)

    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        cfg = self.cfg
        logits = self.forward_train(params, batch)        # (B,S,V) fp32
        if cfg.family == "encoder" or not cfg.is_causal:
            targets = batch["labels"]
            valid = targets >= 0
            tgt = jnp.where(valid, targets, 0)
            nll = self._nll(logits, tgt)
            loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
        else:
            targets = batch["tokens"][:, 1:]
            nll = self._nll(logits[:, :-1], targets)
            loss = jnp.mean(nll)
        return loss, {"loss": loss}

    def _nll(self, logits: jax.Array, targets: jax.Array) -> jax.Array:
        lp = jax.nn.log_softmax(logits, axis=-1)
        if self.cfg.onehot_loss:
            # iota-compare one-hot + contraction: under a vocab-sharded
            # layout this lowers to a tiny (B,S) partial-sum all-reduce
            # instead of materializing/gathering the full logits.
            V = logits.shape[-1]
            onehot = (targets[..., None]
                      == jax.lax.broadcasted_iota(jnp.int32, (V,), 0)
                      ).astype(lp.dtype)
            return -jnp.einsum("bsv,bsv->bs", lp, onehot)
        return -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]

    def prefill(self, params: Params, batch: Dict[str, jax.Array]):
        """Returns (last-position logits, decode cache)."""
        x = self._embed_inputs(params, batch)
        with jax.named_scope("layers"):
            x, caches = self._apply_stack_prefill(params, x)
        logits = layers.unembed(params["embed"], self.cfg, x[:, -1:])[:, 0]
        return logits, caches

    def decode_step(self, params: Params, cache: Any, tokens: jax.Array,
                    lengths: jax.Array, return_hidden: bool = False):
        """tokens (B,) int32, lengths (B,). Returns (logits (B,V), cache)."""
        x = layers.embed(params["embed"], self.cfg, tokens)
        with jax.named_scope("layers"):
            x, new_cache = self._apply_stack_decode(params, cache, x,
                                                    lengths)
        logits = layers.unembed(params["embed"], self.cfg, x[:, None])[:, 0]
        if return_hidden:
            return logits, new_cache, x
        return logits, new_cache

    # -- cache construction ----------------------------------------------------

    def cache_spec(self, batch: int, max_seq: int):
        cfg = self.cfg
        def stack(spec, n):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), spec)
        if self._hybrid:
            g = {f"b{i}": _block_cache_spec(cfg, kind, batch, max_seq)
                 for i, kind in enumerate(self.group_kinds)}
            return {"groups": stack(g, self.n_groups),
                    "tail": {f"t{i}": _block_cache_spec(cfg, kind, batch,
                                                        max_seq)
                             for i, kind in enumerate(self.tail_kinds)}}
        kind = self._kind(0)
        return stack(_block_cache_spec(cfg, kind, batch, max_seq),
                     cfg.n_layers)

    def init_cache(self, batch: int, max_seq: int):
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.cache_spec(batch, max_seq))


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(e, str) for e in x)


def _tree_index(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
