"""launch.steps bundles execute end-to-end on a local (1,1) mesh."""
import contextlib
import dataclasses as dc
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.configs.shapes import ShapeConfig
from repro.launch import steps as steplib
from repro.launch.mesh import make_local_mesh
from repro.models import build_model


@pytest.fixture(scope="module")
def mesh():
    return make_local_mesh()


def _jit(mesh, bundle):
    with mesh:
        return jax.jit(
            bundle.fn,
            in_shardings=steplib.to_shardings(mesh, bundle.in_shardings),
            out_shardings=steplib.to_shardings(mesh, bundle.out_shardings),
            donate_argnums=bundle.donate_argnums)


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-780m",
                                  "deepseek-v2-236b"])
def test_train_step_executes(arch, mesh, rng):
    cfg = configs.get_smoke(arch)
    shape = ShapeConfig("t", seq_len=16, global_batch=2, kind="train")
    bundle = steplib.make_train_step(cfg, shape, mesh)
    model = bundle.meta["model"]
    params = model.init(jax.random.PRNGKey(0))
    from repro.training.optimizer import init_opt_state
    state = {"params": params,
             "opt": init_opt_state(params, cfg.opt_state_dtype,
                                   factored=cfg.opt_factored)}
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}
    fn = _jit(mesh, bundle)
    state2, metrics = fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2["opt"]["step"]) == 1


def test_serve_step_executes(mesh, rng):
    cfg = configs.get_smoke("granite-3-2b")
    shape = ShapeConfig("d", seq_len=32, global_batch=2, kind="decode")
    bundle = steplib.make_serve_step(cfg, shape, mesh)
    model = bundle.meta["model"]
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(2, 32)
    toks = jnp.array([3, 5], jnp.int32)
    lengths = jnp.zeros((2,), jnp.int32)
    fn = _jit(mesh, bundle)
    nxt, cache2 = fn(params, cache, toks, lengths)
    assert nxt.shape == (2,) and nxt.dtype == jnp.int32


def test_prefill_step_executes_encoder(mesh, rng):
    cfg = configs.get_smoke("hubert-xlarge")
    shape = ShapeConfig("p", seq_len=16, global_batch=2, kind="prefill")
    bundle = steplib.make_prefill_step(cfg, shape, mesh)
    model = bundle.meta["model"]
    params = model.init(jax.random.PRNGKey(0))
    batch = {"features": jnp.zeros((2, 16, cfg.frontend_dim), jnp.bfloat16),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    fn = _jit(mesh, bundle)
    logits = fn(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_grad_accum_matches_single_shot(mesh, rng):
    """accum_steps=2 must reproduce the accum=1 loss (same tokens)."""
    cfg = dc.replace(configs.get_smoke("granite-3-2b"),
                     param_dtype=jnp.float32, compute_dtype=jnp.float32)
    shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)}
    losses = {}
    for accum in (1, 2):
        bundle = steplib.make_train_step(cfg, shape, mesh,
                                         accum_steps=accum)
        model = bundle.meta["model"]
        params = model.init(jax.random.PRNGKey(0))
        from repro.training.optimizer import init_opt_state
        state = {"params": params, "opt": init_opt_state(params)}
        _, metrics = _jit(mesh, bundle)(state, batch)
        losses[accum] = float(metrics["loss"])
    assert losses[1] == pytest.approx(losses[2], rel=1e-5)


# -- named scopes: stage names in the compiled steps' metadata ---------------

STEP_SCOPES = {     # arch -> scopes that both steps carry
    "granite-3-2b": {"embed", "layers", "attn", "qkv", "attn_kernel",
                     "attn_out", "mlp", "unembed"},
    "deepseek-v2-236b": {"embed", "layers", "mla", "moe", "unembed"},
    "recurrentgemma-9b": {"embed", "layers", "rglru", "attn", "qkv",
                          "attn_kernel", "attn_out", "mlp", "unembed"},
    "mamba2-780m": {"embed", "layers", "ssd", "unembed"},
}
SCOPE_CASES = [("granite-3-2b", False)] + [(a, True) for a in STEP_SCOPES]
_META = re.compile(r',? metadata=\{(?:[^{}"]|"[^"]*")*\}')
_FRAMES = re.compile(r'^(FileNames|FunctionNames|FileLocations|StackFrames'
                     r'|\d+ .*)$')


def _step_text(mesh, arch, scan, kind):
    cfg = dc.replace(configs.get_smoke(arch), scan_layers=scan)
    if kind == "serve":
        bundle = steplib.make_serve_step(
            cfg, ShapeConfig("d", seq_len=32, global_batch=2, kind="decode"),
            mesh)
    else:
        bundle = steplib.make_prefill_step(
            cfg, ShapeConfig("p", seq_len=16, global_batch=2,
                             kind="prefill"), mesh)
    with mesh:
        return _jit(mesh, bundle).lower(*bundle.input_specs).compile() \
            .as_text()


def _without_metadata(text):
    """The compiled text without op metadata and source locations, its
    instructions renamed in order of appearance (the numeric suffixes XLA
    gives are labels, and scopes can shift them)."""
    lines = [ln for ln in _META.sub("", text).splitlines()
             if not _FRAMES.match(ln)]
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%i{len(names)}"),
                  "\n".join(lines))


@pytest.mark.parametrize("arch,scan", SCOPE_CASES)
@pytest.mark.parametrize("kind", ["serve", "prefill"])
def test_steps_name_their_stages(mesh, arch, scan, kind):
    text = _step_text(mesh, arch, scan, kind)
    found = {part for op in re.findall(r'op_name="([^"]*)"', text)
             for part in op.split("/")}
    want = set(STEP_SCOPES[arch])
    if kind == "serve":
        want |= {"sample"} | ({"kv_write"} if "qkv" in want else set())
    assert want <= found, want - found


@pytest.mark.parametrize("arch,scan", SCOPE_CASES)
@pytest.mark.parametrize("kind", ["serve", "prefill"])
def test_scopes_leave_the_compiled_steps_unchanged(mesh, monkeypatch, arch,
                                                   scan, kind):
    scoped = _step_text(mesh, arch, scan, kind)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _step_text(mesh, arch, scan, kind)
    assert "layers/" in scoped and "layers/" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


def test_engine_commit_is_named():
    from repro.serving.engine import ServingEngine
    old = {"k": jnp.zeros((2, 4, 8))}
    text = jax.jit(ServingEngine._commit).lower(
        old, old, jnp.array([True, False, True, False])).as_text(
            debug_info=True)
    assert "commit/" in text


# -- the serve step's cache: the one new position written into the stack ----

def _fp32(arch, **kw):
    return dc.replace(configs.get_smoke(arch), param_dtype=jnp.float32,
                      compute_dtype=jnp.float32, **kw)


def _decode_after_prefill(model, params, toks, max_seq):
    """Logits of a prefill over ``toks`` and of a decode step that feeds
    its last token after a prefill over the rest; the cache widened to
    ``max_seq`` positions (a ring cache keeps its window)."""
    want, _ = model.prefill(params, {"tokens": toks})
    _, cache = model.prefill(params, {"tokens": toks[:, :-1]})

    def widen(z, c):
        if c.ndim < 4 or z.shape == c.shape:
            return c
        return z.at[:, :, :c.shape[2]].set(c)
    cache = jax.tree_util.tree_map(
        widen, model.init_cache(toks.shape[0], max_seq), cache)
    lengths = jnp.full((toks.shape[0],), toks.shape[1] - 1, jnp.int32)
    got, _ = jax.jit(model.decode_step)(params, cache, toks[:, -1], lengths)
    return np.asarray(got), np.asarray(want)


def _scan_carries(model, params, cache, tokens, lengths):
    """The number of leaves each layer scan of the decode step carries."""
    jaxpr = jax.make_jaxpr(model.decode_step)(params, cache, tokens, lengths)
    return [e.params["num_carry"] for e in jaxpr.eqns
            if e.primitive.name == "scan"]


@pytest.mark.parametrize("arch", ["deepseek-7b", "llama4-maverick-400b-a17b"])
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_decode_writes_one_position_into_the_stacked_cache(arch, scan,
                                                           backend, rng):
    """Dense and MoE stacks of attention blocks carry the stacked cache
    through the layers: the step returns it changed only at
    ``[layer, b, lengths[b]]`` of every layer, and its logits are those of
    a prefill over the same tokens."""
    from repro.kernels import ops
    cfg = _fp32(arch, scan_layers=scan)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    cache = jax.tree_util.tree_map(
        lambda s: jnp.asarray(rng.normal(size=s.shape), s.dtype),
        model.cache_spec(B, S))
    lengths = jnp.array([3, 9], jnp.int32)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B,)), jnp.int32)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 9)), jnp.int32)
    with ops.backend(backend):
        _, new = jax.jit(model.decode_step)(params, cache, tokens, lengths)
        got, want = _decode_after_prefill(model, params, toks, S)
    written = np.zeros((cfg.n_layers, B, S), bool)
    written[:, np.arange(B), np.asarray(lengths)] = True
    for name in ("k", "v"):
        changed = np.any(np.asarray(new[name]) != np.asarray(cache[name]),
                         axis=(-2, -1))
        np.testing.assert_array_equal(changed, written)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    carries = _scan_carries(model, params, cache, tokens, lengths)
    assert carries == ([1 + len(cache)] if scan else [])


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "mamba2-780m",
                                  "recurrentgemma-9b"])
def test_other_caches_keep_the_layer_scan(arch, rng):
    """MLA, SSD and hybrid (windowed ring) caches still go through the scan
    as ``xs`` and ``ys``, and still decode as a prefill over the same
    tokens computes."""
    cfg = _fp32(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 7)), jnp.int32)
    got, want = _decode_after_prefill(model, params, toks, S)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    lengths = jnp.zeros((B,), jnp.int32)
    carries = _scan_carries(model, params, model.init_cache(B, S),
                            lengths, lengths)
    assert carries and all(c == 1 for c in carries)
