"""The architecture's pieces, chosen by ``model_type``
(``families/<model_type>.py``): the llama family computes what the harness
computed before it was split out, to the bit where the arithmetic is
exact; and a family of two stacks of different layer kinds, which lives
only in a test's directory, is found by ``spec``, ``weights``,
``reference`` and ``work``."""
import hashlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import peaks, reference, spec, weights, work
from conftest import TINY

SEED = 2 ** 31 + 12345          # past 32 signed bits


# -- llama: the values the harness gave before the split ----------------------

def tiny_cfg():
    cfg = dict(spec.load_json(spec.HERE / "configs" / "deepseek-7b.json"))
    cfg.update(TINY)
    return cfg


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_llama_weights_are_the_bits_they_were():
    assert digest(weights.make(tiny_cfg(), SEED)) == \
        "064cc20483cf082a8ff803429cf1d32b78ce58e19a0ed205ccf363326930ea70"


# the served gaps of a fixed token array and the fp8 control's, from the
# harness before the split; the CPU's float32 differs in the last bits
# between thread settings
SERVED = [4.312319, 3.48112, 3.777335, 3.26475, 2.316177, 3.70191, 1.852321,
          3.176909, 4.044221, 4.501098, 2.222414, 2.293548, 2.737949,
          2.772897, 3.435252, 3.673338, 0.979192, 3.086719, 2.570154,
          1.902237, 2.039843, 1.950681, 2.699057, 2.47057]
CONTROL = [0.0, 0.0, 0.167715, 0.02108, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
           0.022683, 0.0, 0.0, 0.142381, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
           0.0, 0.188981, 0.524769]


def test_llama_reference_gaps_are_what_they_were():
    tokens = np.random.default_rng(7).integers(1, 256, (2, 24)).astype(
        np.int32)
    targets = np.full((2, 24), -1, np.int32)
    targets[:, 8:20] = tokens[:, 9:21]
    served, low = reference.gaps(tiny_cfg(), SEED, tokens, targets,
                                 control=True)
    np.testing.assert_allclose(served[np.isfinite(served)], SERVED,
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(low[np.isfinite(low)], CONTROL, rtol=0,
                               atol=1e-5)


def test_llama_work_counts_are_what_they_were():
    """The batch cell's request shape on the cell's configuration."""
    cfg = spec.load_json(spec.HERE / "configs" / "deepseek-7b.json")
    pk = peaks.lookup("TPU v5 lite")
    reqs = [dict(hist=0, prompt=[1] * 256, fed=511, prefilled=256)] * 16
    assert work.model_flops(cfg, 0, 511, 256, 256) == 3349309030400.0
    assert work.least_decode_attention_seconds(cfg, reqs, pk) == \
        0.470130778021978
    assert work.least_prefill_attention_seconds(cfg, reqs, pk) == \
        0.0024582001465201465


# -- a family of two stacks, in a test's directory ----------------------------

TOY = textwrap.dedent('''
    """A leading layer with a wide MLP, then layers of routed experts whose
    expert leaves are drawn per slice: the chip holds ``held``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.weights import NORM_SPREAD, Leaf, Stack


    def model_config(cfg):
        return {"toy": cfg["name"]}


    def stacks(cfg):
        d, F, f = cfg["hidden_size"], cfg["dense_width"], cfg["expert_width"]
        held = cfg["held"]
        first = (Leaf("mlp", "ln", (d,), NORM_SPREAD, True),
                 Leaf("mlp", "wi", (d, F), d ** -0.5),
                 Leaf("mlp", "wo", (F, d), F ** -0.5))
        moe = (Leaf("moe", "ln", (d,), NORM_SPREAD, True),
               Leaf("moe", "router", (d, cfg["experts"]), d ** -0.5),
               Leaf("moe", "wi", (len(held), d, f), d ** -0.5, slices=held),
               Leaf("moe", "wo", (len(held), f, d), f ** -0.5, slices=held))
        return [Stack("first", 1, first),
                Stack("experts", cfg["num_hidden_layers"] - 1, moe)]


    def embed_leaves(cfg):
        d, v = cfg["hidden_size"], cfg["vocab_size"]
        return [Leaf(None, "tok_embed", (v, d), d ** -0.5),
                Leaf(None, "unembed", (d, v), d ** -0.5)]


    def _norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-6) * scale


    def layer(cfg, stack, w, x, q8w, q8a):
        if stack == "first":
            p = w["mlp"]
            h = q8a(_norm(x, p["ln"]))
            return x + q8a(jax.nn.silu(h @ q8w(p["wi"]))) @ q8w(p["wo"])
        p = w["moe"]
        h = q8a(_norm(x, p["ln"]))
        gate = jax.nn.softmax(h @ q8w(p["router"]), -1)[..., cfg["held"]]
        a = q8a(jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, q8w(p["wi"]))))
        return x + jnp.einsum("bsef,efd,bse->bsd", a, q8w(p["wo"]), gate)


    def logits(cfg, e, x, q8w, q8a):
        return q8a(x) @ q8w(e["unembed"])


    def reference_rows(cfg):
        return cfg["rows"]


    def matmul_flops(cfg):
        d, F, f = cfg["hidden_size"], cfg["dense_width"], cfg["expert_width"]
        moe = d * cfg["experts"] + cfg["experts_per_token"] * 2 * d * f
        return 2.0 * (2 * d * F + (cfg["num_hidden_layers"] - 1) * moe)


    def head_flops(cfg):
        return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


    def attention_flops(cfg, path):
        per = {"prefill": 20, "decode": 26}[path]
        return 2.0 * cfg["num_hidden_layers"] * per


    def cache_bytes(cfg):
        return cfg["num_hidden_layers"] * 9 * 2


    def prefill_bytes(cfg):
        return cfg["num_hidden_layers"] * 20 * 2
''')

TOY_CFG = dict(name="toy-moe", model_type="toy_moe", hidden_size=8,
               dense_width=16, expert_width=4, experts=4,
               experts_per_token=2, held=[0, 1, 2, 3], num_hidden_layers=3,
               vocab_size=32, rows=None)


@pytest.fixture
def toy(tmp_path, monkeypatch):
    (tmp_path / "toy_moe.py").write_text(TOY)
    monkeypatch.setattr(spec, "FAMILIES", tmp_path)
    return dict(TOY_CFG)


def _leaves_equal(a, b):
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    return all(jax.tree_util.tree_leaves(same))


def test_a_family_of_two_stacks_is_found(toy):
    assert spec.model_config(toy) == {"toy": "toy-moe"}
    whole = weights.make(toy, SEED)
    assert set(whole) == {"embed", "first", "experts"}
    assert whole["first"]["mlp"]["wi"].shape == (1, 8, 16)
    assert whole["experts"]["moe"]["wi"].shape == (2, 4, 8, 4)
    assert weights.kinds(toy) == ["first", "experts", "experts"]
    # the reference, layer by layer, against the family's layers run in
    # order over the whole tree
    tokens = np.random.default_rng(3).integers(0, 32, (3, 10)).astype(
        np.int32)
    fam = spec.family(toy)
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), whole)
    same = lambda a: a
    with jax.default_matmul_precision("highest"):
        x = f32["embed"]["tok_embed"][tokens]
        for i, kind in enumerate(weights.kinds(toy)):
            first = 0 if kind == "first" else 1
            w = jax.tree_util.tree_map(lambda a: a[i - first], f32[kind])
            x = fam.layer(toy, kind, w, x, same, same)
        want = np.asarray(fam.logits(toy, f32["embed"], x, same, same))
    targets = want.argmax(-1).astype(np.int32)
    for rows in (None, 2):
        served, low = reference.gaps(dict(toy, rows=rows), SEED, tokens,
                                     targets, control=True)
        assert np.isfinite(served).all() and np.nanmax(served) < 1e-5
        assert np.isfinite(low).all() and low.min() >= 0


def test_a_family_of_two_stacks_layer_by_layer_equals_the_whole(toy):
    whole = weights.make(toy, SEED)
    assert _leaves_equal(whole["embed"], weights.embed(toy, SEED))
    for i, kind in enumerate(weights.kinds(toy)):
        first = 0 if kind == "first" else 1
        row = jax.tree_util.tree_map(lambda a: a[i - first], whole[kind])
        assert _leaves_equal(row, weights.layer(toy, SEED, i))
    # the two expert layers differ
    wi = whole["experts"]["moe"]["wi"]
    assert not bool((wi[0] == wi[1]).all())


def test_a_slice_drawn_alone_is_its_row_of_the_whole_leaf(toy):
    whole = weights.make(toy, SEED)["experts"]["moe"]
    for held in ([2], [3, 1]):
        share = weights.make(dict(toy, held=held), SEED)["experts"]["moe"]
        for name in ("wi", "wo"):
            assert bool((share[name] == whole[name][:, held]).all())
        assert bool((share["router"] == whole["router"]).all())


def test_a_family_of_two_stacks_work_by_hand(toy):
    """Layers 1 dense + 2 of experts, d 8, F 16, f 4, 4 experts, 2 a
    token, vocabulary 32; a request with no history, 3 positions
    prefilled, then 2 decode inputs at contexts 4 and 5, 2 tokens out."""
    matmul = 2 * (2 * 8 * 16 + 2 * (8 * 4 + 2 * 2 * 8 * 4))
    head = 2 * 8 * 32
    prefill = 2 * 3 * 20 * (1 + 2 + 3)
    decode = 2 * 3 * 26 * (4 + 5)
    assert work.model_flops(toy, 0, 5, 2, 3) == \
        matmul * 5 + head * 2 + prefill + decode
    r = dict(hist=0, prompt=[7, 8, 9], fed=5, prefilled=3)
    flops = {"flops_bf16": 1.0, "hbm_bytes_per_s": 1e30}
    nbytes = {"flops_bf16": 1e30, "hbm_bytes_per_s": 1.0}
    assert work.least_prefill_attention_seconds(toy, [r], flops) == prefill
    assert work.least_decode_attention_seconds(toy, [r], flops) == decode
    assert work.least_prefill_attention_seconds(toy, [r], nbytes) == \
        3 * 20 * 2 * 3
    assert work.least_decode_attention_seconds(toy, [r], nbytes) == \
        3 * 9 * 2 * (4 + 5)


def test_an_unknown_model_type_lists_the_families(toy, monkeypatch):
    with pytest.raises(ValueError, match=r"'mamba'.*\['toy_moe'\]"):
        weights.make(dict(toy, model_type="mamba"), SEED)
    monkeypatch.setattr(spec, "FAMILIES", spec.HERE / "families")
    with pytest.raises(ValueError, match=r"'mamba'.*'llama'"):
        spec.model_config(dict(tiny_cfg(), model_type="mamba"))
