"""Logical-axis -> mesh-axis sharding rules.

Every model parameter carries a tuple of logical axis names (see
``models.common.ParamFactory``).  A ``ShardingRules`` maps logical names to
mesh axis names (or None = replicate); ``specs_for_params`` turns a params
tree + axes tree into a PartitionSpec tree, enforcing divisibility and
no-mesh-axis-reuse per tensor.  This module is the primary perf-hillclimb
knob: per-(arch, shape) overrides live in ``repro.launch.dryrun``'s
CELL_OVERRIDES and are recorded in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisName = Optional[Any]    # None | str | tuple[str, ...]


@dataclasses.dataclass
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes, or None)."""
    rules: Dict[str, AxisName]
    dp_axes: Tuple[str, ...]            # data-parallel axes for activations
    fsdp_axis: Optional[str] = None     # shard params/opt over this axis too
    fsdp_min_size: int = 2 ** 20        # only FSDP tensors >= this many elems

    def mesh_axes_for(self, logical: str) -> Tuple[str, ...]:
        ax = self.rules.get(logical)
        if ax is None:
            return ()
        if isinstance(ax, str):
            return (ax,)
        return tuple(ax)


def default_rules(mesh: Mesh, *, fsdp: bool = False) -> ShardingRules:
    """Baseline TP-over-'model', DP-over-('pod','data') rules."""
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    rules = {
        "vocab": "model",
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "layers": None,
        "norm": None,
        "conv": None,
        "lru": "model",
        "lru_blocks": None,
        "lru_in": None,
        "lru_out": None,
        "q_lora": None,
        "kv_lora": None,
        "ssm_inner": "model",
        "ssm_bc": None,
        "ssm_heads": "model",
        "frontend": None,
    }
    return ShardingRules(rules=rules, dp_axes=dp,
                         fsdp_axis="data" if fsdp else None)


def _axis_size(mesh: Mesh, ax: AxisName) -> int:
    if ax is None:
        return 1
    if isinstance(ax, str):
        return mesh.shape[ax]
    n = 1
    for a in ax:
        n *= mesh.shape[a]
    return n


def spec_for_tensor(mesh: Mesh, rules: ShardingRules,
                    logical: Sequence[str], shape: Sequence[int],
                    n_elems: Optional[int] = None) -> P:
    """Build a PartitionSpec for one tensor, dropping non-divisible axes."""
    used: set = set()
    out = []
    for dim, name in zip(shape, logical):
        ax = rules.rules.get(name)
        axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
        axes = tuple(a for a in axes if a not in used)
        if axes and dim % _axis_size(mesh, axes) == 0:
            used.update(axes)
            out.append(axes[0] if len(axes) == 1 else axes)
        else:
            out.append(None)
    # FSDP: additionally shard the largest still-unsharded dim over fsdp_axis
    n = n_elems if n_elems is not None else _prod(shape)
    if (rules.fsdp_axis and rules.fsdp_axis not in used
            and n >= rules.fsdp_min_size):
        fs = mesh.shape[rules.fsdp_axis]
        cands = sorted(
            (i for i, s in enumerate(out)
             if s is None and shape[i] % fs == 0 and shape[i] >= fs),
            key=lambda i: -shape[i])
        # never FSDP-shard a stacked-layer leading axis (scan carries it)
        cands = [i for i in cands if logical[i] != "layers"]
        if cands:
            out[cands[0]] = rules.fsdp_axis
    return P(*out)


def _prod(xs):
    n = 1
    for x in xs:
        n *= int(x)
    return n


def specs_for_params(mesh: Mesh, rules: ShardingRules, params_shapes: Any,
                     axes_tree: Any) -> Any:
    """PartitionSpec tree matching the params tree."""
    def is_axes_leaf(x):
        return isinstance(x, tuple) and all(isinstance(e, str) for e in x)

    flat_ax, treedef = jax.tree_util.tree_flatten(axes_tree,
                                                  is_leaf=is_axes_leaf)
    flat_sh = treedef.flatten_up_to(params_shapes)
    specs = [spec_for_tensor(mesh, rules, a, s.shape)
             for a, s in zip(flat_ax, flat_sh)]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Activation / input shardings
# ---------------------------------------------------------------------------

def batch_pspec(mesh: Mesh, rules: ShardingRules, batch_size: int,
                extra_dims: int = 1) -> P:
    """Spec for a (batch, ...) input: batch over as many dp axes as divide."""
    dp = []
    rem = batch_size
    for a in rules.dp_axes:
        if rem % mesh.shape[a] == 0:
            dp.append(a)
            rem //= mesh.shape[a]
    first = tuple(dp) if len(dp) > 1 else (dp[0] if dp else None)
    return P(first, *([None] * extra_dims))


def cache_pspecs(mesh: Mesh, rules: ShardingRules, cfg, cache_spec: Any,
                 *, stacked: bool = True) -> Any:
    """PartitionSpec tree for a decode cache.

    Layout per leaf (after optional leading stacked-layers axis):
      k/v:          (B, S, K, D)   -> kv_heads over 'model' if divisible,
                                      else seq over 'model' (flash-decoding)
      ckv/k_rope:   (B, S, L)      -> seq over 'model'
      ssm state:    (B, H, P, N)   -> heads over 'model'
      lru h/conv:   (B, [, c], W)  -> width over 'model'
    """
    tp = mesh.shape["model"]

    def leaf_spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        dims = list(leaf.shape)
        lead = []
        if stacked:
            lead, dims = [None], dims[1:]
        bs = dims[0]
        bspec = batch_pspec(mesh, rules, bs, extra_dims=0)[0]
        rest = [None] * (len(dims) - 1)
        if name in ("k", "v"):
            if dims[2] % tp == 0:
                rest[1] = "model"
            elif dims[1] % tp == 0:
                rest[0] = "model"
        elif name in ("ckv", "k_rope"):
            if dims[1] % tp == 0:
                rest[0] = "model"
        elif name == "state":
            if dims[1] % tp == 0:
                rest[0] = "model"
        elif name in ("h",):
            if dims[1] % tp == 0:
                rest[0] = "model"
        elif name.startswith("conv"):
            if dims[-1] % tp == 0:
                rest[-1] = "model"
        return P(*lead, bspec, *rest)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_spec)
