"""Step builders shared by the dry-run, the trainer, and the server.

``make_train_step`` / ``make_prefill_step`` / ``make_serve_step`` return
(fn, input_specs, in_shardings, out_shardings, donate) bundles ready for
``jax.jit(...).lower(...)``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.shapes import ShapeConfig
from repro.distributed import sharding_rules as sr
from repro.distributed import constraints
from repro.models import build_model
from repro.models.common import ModelConfig
from repro.training import optimizer as opt


def to_shardings(mesh: Mesh, tree: Any) -> Any:
    """PartitionSpec tree -> NamedSharding tree (None passes through)."""
    if tree is None:
        return None
    from jax.sharding import NamedSharding
    return jax.tree_util.tree_map(
        lambda s: None if s is None
        else (NamedSharding(mesh, s) if isinstance(s, P) else s),
        tree, is_leaf=lambda x: isinstance(x, P) or x is None)


@dataclasses.dataclass
class StepBundle:
    fn: Any
    input_specs: Tuple[Any, ...]        # ShapeDtypeStructs (positional)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    meta: Dict[str, Any]


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if cfg.frontend == "audio":
        return {"features": sds((B, S, cfg.frontend_dim), jnp.bfloat16),
                "labels": sds((B, S), jnp.int32)}
    out = {"tokens": sds((B, S), jnp.int32)}
    if cfg.frontend == "vision":
        out["patches"] = sds((B, cfg.n_patches, cfg.frontend_dim),
                             jnp.bfloat16)
    return out


def batch_pspecs(mesh: Mesh, rules: sr.ShardingRules, cfg: ModelConfig,
                 shape: ShapeConfig) -> Dict[str, Any]:
    B = shape.global_batch
    out = {}
    for k, v in batch_specs(cfg, shape).items():
        out[k] = sr.batch_pspec(mesh, rules, B, extra_dims=len(v.shape) - 1)
    return out


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                    rules: Optional[sr.ShardingRules] = None,
                    ocfg: Optional[opt.AdamWConfig] = None,
                    accum_steps: int = 1,
                    constrain_grads: bool = False) -> StepBundle:
    constraints.set_mesh(mesh)
    model = build_model(cfg)
    rules = rules or sr.default_rules(mesh)
    ocfg = ocfg or opt.AdamWConfig()

    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    axes = model.param_axes()
    pspecs = sr.specs_for_params(mesh, rules, params_shapes, axes)
    opt_shapes = jax.eval_shape(
        functools.partial(opt.init_opt_state,
                          state_dtype=cfg.opt_state_dtype,
                          factored=cfg.opt_factored),
        params_shapes)

    def v_spec(ps, p):
        if cfg.opt_factored and p.ndim >= 2 and p.shape[-1] > 1 \
                and p.shape[-2] > 1:
            t = tuple(ps)
            return {"vr": P(*t[:-1]), "vc": P(*t[:-2], t[-1])}
        return ps
    vspecs = jax.tree_util.tree_map(
        v_spec, pspecs, params_shapes,
        is_leaf=lambda x: isinstance(x, P))
    mspecs = {"m": pspecs, "v": vspecs, "step": P()}
    state_shapes = {"params": params_shapes, "opt": opt_shapes}
    state_specs = {"params": pspecs, "opt": mspecs}

    bspecs = batch_specs(cfg, shape)
    bpspecs = batch_pspecs(mesh, rules, cfg, shape)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    if cfg.opt_factored and (ocfg is None or not ocfg.factored):
        ocfg = dataclasses.replace(ocfg or opt.AdamWConfig(), factored=True)

    def train_step(state, batch):
        params = state["params"]
        if accum_steps > 1:
            def micro(carry, mb):
                (l, g) = carry
                (li, mi), gi = grad_fn(params, mb)
                g = jax.tree_util.tree_map(jnp.add, g, gi)
                return (l + li, g), None
            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            micro_batch = jax.tree_util.tree_map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                    + x.shape[1:]), batch)
            (tot_l, grads), _ = jax.lax.scan(micro, (0.0, zero_g), micro_batch)
            loss = tot_l / accum_steps
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
            metrics = {"loss": loss}
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        if constrain_grads:
            # pin grads to the param layout: GSPMD then reduce-scatters
            # partial grads onto the FSDP shards instead of all-reducing
            # full fp32 tensors (observed 5 GB/expert-tensor reduces in
            # the dsv2 baseline — EXPERIMENTS.md §Perf).
            gshard = to_shardings(mesh, pspecs)
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads, gshard)
        new_params, new_opt, ometrics = opt.adamw_update(
            ocfg, grads, params, state["opt"])
        metrics = dict(metrics, **ometrics)
        return {"params": new_params, "opt": new_opt}, metrics

    return StepBundle(
        fn=train_step,
        input_specs=(state_shapes, bspecs),
        in_shardings=(state_specs, bpspecs),
        out_shardings=(state_specs, None),
        donate_argnums=(0,),
        meta={"model": model, "pspecs": pspecs, "rules": rules,
              "state_specs": state_specs, "batch_pspecs": bpspecs},
    )


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                      rules: Optional[sr.ShardingRules] = None) -> StepBundle:
    constraints.set_mesh(mesh)
    model = build_model(cfg)
    rules = rules or sr.default_rules(mesh)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sr.specs_for_params(mesh, rules, params_shapes,
                                 model.param_axes())
    bspecs = batch_specs(cfg, shape)
    bpspecs = batch_pspecs(mesh, rules, cfg, shape)

    if cfg.family == "encoder":
        def prefill(params, batch):
            return model.forward_train(params, batch)
        cache_out = None
    else:
        def prefill(params, batch):
            return model.prefill(params, batch)
        cache_shapes = model.cache_spec(shape.global_batch, shape.seq_len)
        cache_out = sr.cache_pspecs(mesh, rules, cfg, cache_shapes,
                                    stacked=not getattr(model, "_hybrid"))
        if getattr(model, "_hybrid"):
            cache_out = _hybrid_cache_specs(mesh, rules, cfg, model,
                                            cache_shapes)

    out_shardings = None if cfg.family == "encoder" else (None, cache_out)
    return StepBundle(
        fn=prefill,
        input_specs=(params_shapes, bspecs),
        in_shardings=(pspecs, bpspecs),
        out_shardings=out_shardings,
        donate_argnums=(),
        meta={"model": model, "pspecs": pspecs, "rules": rules},
    )


def _hybrid_cache_specs(mesh, rules, cfg, model, cache_shapes):
    groups = sr.cache_pspecs(mesh, rules, cfg, cache_shapes["groups"],
                             stacked=True)
    tail = sr.cache_pspecs(mesh, rules, cfg, cache_shapes["tail"],
                           stacked=False)
    return {"groups": groups, "tail": tail}


# ---------------------------------------------------------------------------
# serve (decode)
# ---------------------------------------------------------------------------

def make_serve_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                    rules: Optional[sr.ShardingRules] = None) -> StepBundle:
    assert cfg.family != "encoder", "encoder archs have no decode step"
    constraints.set_mesh(mesh)
    model = build_model(cfg)
    rules = rules or sr.default_rules(mesh)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sr.specs_for_params(mesh, rules, params_shapes,
                                 model.param_axes())
    B, S = shape.global_batch, shape.seq_len
    cache_shapes = model.cache_spec(B, S)
    if getattr(model, "_hybrid"):
        cspecs = _hybrid_cache_specs(mesh, rules, cfg, model, cache_shapes)
    else:
        cspecs = sr.cache_pspecs(mesh, rules, cfg, cache_shapes, stacked=True)
    sds = jax.ShapeDtypeStruct
    tok_spec = sds((B,), jnp.int32)
    len_spec = sds((B,), jnp.int32)
    bp = sr.batch_pspec(mesh, rules, B, extra_dims=0)

    def serve_step(params, cache, tokens, lengths):
        logits, new_cache = model.decode_step(params, cache, tokens, lengths)
        with jax.named_scope("sample"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    return StepBundle(
        fn=serve_step,
        input_specs=(params_shapes, cache_shapes, tok_spec, len_spec),
        in_shardings=(pspecs, cspecs, bp, bp),
        out_shardings=(bp, cspecs),
        donate_argnums=(1,),
        meta={"model": model, "pspecs": pspecs, "rules": rules,
              "cache_specs": cspecs},
    )


def make_step(kind: str, cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
              rules: Optional[sr.ShardingRules] = None, **kw) -> StepBundle:
    if kind == "train":
        return make_train_step(cfg, shape, mesh, rules, **kw)
    if kind == "prefill":
        return make_prefill_step(cfg, shape, mesh, rules)
    if kind == "decode":
        return make_serve_step(cfg, shape, mesh, rules)
    raise ValueError(kind)
