"""Ahead-of-time compiles for a described (not attached) TPU v5e.

The chip's compiler is installed with JAX, so these tests compile the
served kernels and the full-width granite-3-2b decode step exactly as the
chip would, and fail on whatever it refuses (tile-misaligned blocks, too
much VMEM, a program that does not fit HBM).  Nothing runs; results and
times are not checked here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.configs.shapes import ShapeConfig
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.launch import steps as steplib
from repro.launch.mesh import make_local_mesh
from repro.models import build_model

HBM_BYTES = 16 * 2 ** 30           # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# granite-3-2b: 32 query heads, 8 kv heads, head_dim 64; 8 slots x 2048
GRANITE = dict(B=8, S=2048, H=32, K=8, D=64)


def _decode_attention(sh):
    g = GRANITE
    return (lambda q, k, v, n: decode_attention(q, k, v, n),
            _spec(sh, (g["B"], g["H"], g["D"])),
            _spec(sh, (g["B"], g["S"], g["K"], g["D"])),
            _spec(sh, (g["B"], g["S"], g["K"], g["D"])),
            _spec(sh, (g["B"],), jnp.int32))


def _flash_attention(sh):
    g = GRANITE
    return (lambda q, k, v: flash_attention(q, k, v),
            _spec(sh, (1, g["S"], g["H"], g["D"])),
            _spec(sh, (1, g["S"], g["K"], g["D"])),
            _spec(sh, (1, g["S"], g["K"], g["D"])))


def _ssd_scan(sh):
    # mamba2-780m: d_inner 3072 = 48 heads x 64, state 128, 1 group
    cfg = configs.get_config("mamba2-780m")
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    H = cfg.ssm_expand * cfg.d_model // P
    B, S, G = 1, 2048, cfg.ssm_ngroups
    return (lambda x, dt, A, Bm, Cm, D: ssd_scan(x, dt, A, Bm, Cm, D,
                                                 chunk=cfg.ssm_chunk),
            _spec(sh, (B, S, H, P)), _spec(sh, (B, S, H), jnp.float32),
            _spec(sh, (H,), jnp.float32), _spec(sh, (B, S, G, N)),
            _spec(sh, (B, S, G, N)), _spec(sh, (H,), jnp.float32))


def _rglru_scan(sh):
    # recurrentgemma-9b: lru_width 4096
    W = configs.get_config("recurrentgemma-9b").lru_width
    B, S = 2, 2048
    return (lambda a, b, h0: rglru_scan(a, b, h0),
            _spec(sh, (B, S, W)), _spec(sh, (B, S, W)), _spec(sh, (B, W)))


@pytest.mark.parametrize("case", [_decode_attention, _flash_attention,
                                  _ssd_scan, _rglru_scan],
                         ids=lambda c: c.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, *args = case(one_chip)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_decode_step_compiles_for_v5e(one_chip):
    """The served step at published widths, Pallas decode attention in it,
    fits one chip's HBM."""
    model = build_model(configs.get_config("granite-3-2b"))
    B, S = GRANITE["B"], GRANITE["S"]
    on_chip = lambda s: _spec(one_chip, s.shape, s.dtype)
    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.tree_util.tree_map(on_chip, model.cache_spec(B, S))
    tokens = _spec(one_chip, (B,), jnp.int32)
    with ops.backend("pallas"):
        compiled = _compile(model.decode_step, params, cache, tokens, tokens)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes < HBM_BYTES


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                          r"([\w\-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def test_serve_step_writes_the_stacked_cache_in_place(one_chip):
    """The serve step at deepseek-7b widths (3 layers, 16 rows x 512
    positions, the cache donated) neither slices a layer's K/V cache out
    of the stack and writes it back, nor copies the stack: its only
    instructions that yield the stack are the in-place ``kv_write``
    scatters, and its temporaries stay under one layer's K cache."""
    L, B, S = 3, 16, 512
    cfg = dataclasses.replace(configs.get_config("deepseek-7b"), n_layers=L)
    bundle = steplib.make_serve_step(cfg, ShapeConfig("d", S, B, "decode"),
                                     make_local_mesh())
    args = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype), bundle.input_specs)
    with ops.backend("pallas"):
        compiled = jax.jit(bundle.fn, donate_argnums=bundle.donate_argnums) \
            .lower(*args).compile()
    K, D = cfg.n_kv_heads, cfg.resolved_head_dim
    layer, stack = f"bf16[{B},{S},{K},{D}]", f"bf16[{L},{B},{S},{K},{D}]"
    moves, writes = [], 0
    for line in compiled.as_text().splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(2) not in (layer, stack):
            continue
        name, shape, opcode = m.groups()
        if opcode in _MOVES or (opcode == "fusion"
                                and any(w in name for w in _MOVES)):
            moves.append(f"{name} = {shape} {opcode}")
        writes += opcode == "fusion" and "/kv_write/" in line
    assert not moves, moves
    assert writes == 2                              # K and V, in the scan
    one_layer_k = B * S * K * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_k
