"""Ahead-of-time compiles, for a described (not attached) TPU v5e, of what
each cell runs on the chip: the program's prefill and serve steps at the
batch cell's shapes, the engine's decode step at the sessions cell's slots
and positions, the seeded weight maker, and the reference's layer at the
widest block of rows a check runs.

Nothing runs; a configuration that the chip's compiler refuses, or that
does not fit one chip's memory, fails here before it costs chip time.  The
topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.chip import reference, spec, weights
from conftest import SESSIONS_CELL

HBM_BYTES = 16 * 2 ** 30
BATCH_CELLS = [w["name"] for w in
               spec.load_json(spec.ROOT / "BENCHMARK.json")["workloads"]
               if spec.cell(w["name"])["traffic"]["kind"] == "batch"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv("TPU_LOG_DIR", "disabled")
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()       # a Pallas kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("name", BATCH_CELLS)
def test_batch_steps_compile_at_the_cell_shapes(one_chip, name):
    from repro.kernels import ops
    from repro.models import build_model
    cell = spec.cell(name)
    cfg, mix = cell["config"], cell["traffic"]
    B, P, G = mix["batch"], mix["prompt"], mix["answer"]
    model = build_model(spec.model_config(cfg))
    params = _on(one_chip, jax.eval_shape(lambda: weights.make(cfg, 1)))
    prompts = jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one_chip)
    cache = _on(one_chip, model.cache_spec(B, P + G))
    toks = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def serve(params, cache, tokens, lengths):
        logits, new_cache = model.decode_step(params, cache, tokens, lengths)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_cache

    with ops.backend("pallas"):
        _fits(jax.jit(model.prefill).lower(params,
                                           {"tokens": prompts}).compile())
        _fits(jax.jit(serve, donate_argnums=1).lower(
            params, cache, toks, toks).compile())


def test_engine_decode_step_compiles_at_the_sessions_cell_shapes(one_chip):
    from repro.kernels import ops
    from repro.models import build_model
    cfg = spec.load_json(spec.HERE / "configs"
                         / f"{SESSIONS_CELL['config']}.json")
    slots, max_seq = (cfg["serving"]["slots_per_row"],
                      cfg["serving"]["max_seq"])
    model = build_model(spec.model_config(cfg))
    params = _on(one_chip, jax.eval_shape(lambda: weights.make(cfg, 1)))
    cache = _on(one_chip, model.cache_spec(slots, max_seq))
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    with ops.backend("pallas"):
        _fits(jax.jit(model.decode_step).lower(params, cache, toks,
                                               toks).compile())


@pytest.mark.parametrize("name", BATCH_CELLS)
def test_weights_and_reference_compile(one_chip, name):
    cell = spec.cell(name)
    cfg, mix = cell["config"], cell["traffic"]
    make, _, _ = weights._jitted(weights._freeze(cfg))
    words = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    mem = make.lower(words).compile().memory_analysis()
    assert mem.output_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < 2 ** 30          # made in one fused pass
    layer = _on(one_chip, jax.eval_shape(lambda: weights.layer(cfg, 1, 0)))
    x = jax.ShapeDtypeStruct((mix["compare_requests"],
                              mix["prompt"] + mix["answer"],
                              cfg["hidden_size"]), jnp.float32,
                             sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        for low in (False, True):
            fn = reference._programs(weights._freeze(cfg), low)[1]
            mem = fn.lower(layer, x).compile().memory_analysis()
            assert mem.temp_size_in_bytes < HBM_BYTES // 4
