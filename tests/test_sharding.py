"""Sharding rules: divisibility, FSDP, cache specs (AbstractMesh: no
devices needed)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.distributed import sharding_rules as sr
from repro.models import build_model

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_divisible_axis_sharded():
    rules = sr.default_rules(MESH)
    spec = sr.spec_for_tensor(MESH, rules, ("embed", "mlp"), (2048, 8192))
    assert spec == P(None, "model")


def test_spec_non_divisible_axis_dropped():
    rules = sr.default_rules(MESH)
    # 40 heads not divisible by model=16 -> replicated
    spec = sr.spec_for_tensor(MESH, rules, ("embed", "heads", "head_dim"),
                              (5120, 40, 128))
    assert spec[1] is None


def test_fsdp_shards_largest_free_dim():
    rules = sr.default_rules(MESH, fsdp=True)
    spec = sr.spec_for_tensor(MESH, rules, ("experts", "embed", "mlp"),
                              (160, 5120, 1536))
    assert spec == P("model", "data", None)


def test_fsdp_skips_small_tensors():
    rules = sr.default_rules(MESH, fsdp=True)
    spec = sr.spec_for_tensor(MESH, rules, ("norm",), (4096,))
    assert spec == P(None)


def test_no_axis_reuse_within_tensor():
    rules = sr.default_rules(MESH)
    rules.rules["embed"] = "model"
    spec = sr.spec_for_tensor(MESH, rules, ("embed", "mlp"), (2048, 8192))
    used = [s for s in spec if s is not None]
    assert len(used) == len(set(used))


def test_batch_pspec_multi_pod():
    rules = sr.default_rules(MESH3)
    spec = sr.batch_pspec(MESH3, rules, 256, extra_dims=1)
    assert spec == P(("pod", "data"), None)


def test_batch_pspec_indivisible_batch():
    rules = sr.default_rules(MESH)
    spec = sr.batch_pspec(MESH, rules, 1, extra_dims=0)
    assert spec == P(None)


def test_params_specs_cover_whole_tree():
    cfg = configs.get_smoke("deepseek-v2-236b")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    axes = model.param_axes()
    specs = sr.specs_for_params(MESH, sr.default_rules(MESH), shapes, axes)
    n_specs = len(jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))
    n_params = len(jax.tree_util.tree_leaves(shapes))
    assert n_specs == n_params


def test_cache_pspec_kv_heads_or_seq():
    cfg = configs.get_config("granite-3-2b")   # kv=8, not divisible by 16
    model = build_model(cfg)
    spec_tree = model.cache_spec(128, 1024)
    rules = sr.default_rules(MESH)
    specs = sr.cache_pspecs(MESH, rules, cfg, spec_tree, stacked=True)
    k_spec = specs["k"]
    # kv_heads=8 not divisible -> seq dim sharded instead (flash-decoding)
    assert k_spec == P(None, "data", "model", None, None)


def test_cache_pspec_divisible_kv_heads():
    cfg = configs.get_config("deepseek-7b")    # kv=32 divisible by 16
    model = build_model(cfg)
    spec_tree = model.cache_spec(128, 1024)
    specs = sr.cache_pspecs(MESH, sr.default_rules(MESH), cfg, spec_tree,
                            stacked=True)
    assert specs["k"] == P(None, "data", None, "model", None)


def test_production_mesh_constants():
    from repro.launch import mesh as meshlib
    assert meshlib.PEAK_FLOPS_BF16 == 197e12
    assert meshlib.HBM_BW == 819e9
    assert meshlib.ICI_BW_PER_LINK == 50e9
