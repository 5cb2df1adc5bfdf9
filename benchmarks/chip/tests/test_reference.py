"""Work counts, seeded weights and the float32 reference, against the
program (``repro``) at small sizes on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import (batch, calibrate, reference, sessions, spec,
                              traffic, weights, work)
from conftest import TINY, TINY_MIX

SEED = 2 ** 31 + 12345          # past 32 signed bits


def tiny_cfg(**over):
    cfg = dict(spec.load_json(spec.HERE / "configs" / "deepseek-7b.json"))
    cfg.update(TINY, **over)
    return cfg


def test_layer_params_match_the_program_count():
    from repro.models.common import ModelConfig
    for name in ("deepseek-7b",):
        cfg = spec.load_json(spec.HERE / "configs" / f"{name}.json")
        mc = spec.model_config(cfg)
        emb = cfg["vocab_size"] * cfg["hidden_size"] * (
            1 if cfg["tie_word_embeddings"] else 2)
        assert ModelConfig.param_count(mc) - emb == \
            cfg["num_hidden_layers"] * work.layer_matmul_params(cfg)


def test_kv_bytes_match_the_program_cache():
    from repro.models import build_model
    from repro.serving import kv_cache
    for name, per_pos in (("deepseek-7b", 240 * 1024),):
        cfg = spec.load_json(spec.HERE / "configs" / f"{name}.json")
        model = build_model(spec.model_config(cfg))
        assert work.kv_bytes_per_position(cfg) == per_pos == \
            kv_cache.session_cache_bytes(model, 64) // 64


def test_attention_counts_by_hand():
    cfg = tiny_cfg()
    kvb = work.kv_bytes_per_position(cfg)
    # history 3, two fed positions: contexts 4 and 5
    assert work.attention_flops(cfg, 3, 2) == 4 * 2 * 4 * 16 * 9
    # history 3 + prompt 2 read once; decode inputs read 6 and 7
    assert work.attention_bytes(cfg, 3, 2, 2) == kvb * (5 + 6 + 7)
    # no prompt pass: only the decode inputs' contexts, 4 and 5
    assert work.attention_bytes(cfg, 3, 0, 2) == kvb * (4 + 5)
    t = dict(hist=3, prompt=[1, 2], fed=4)
    peaks = {"flops_bf16": 1.0, "hbm_bytes_per_s": 1e30}
    assert work.least_decode_attention_seconds(cfg, [t], peaks) == \
        work.attention_flops(cfg, 3, 4)
    assert work.least_prefill_attention_seconds(cfg, [t], peaks) == 0


def test_prefill_and_decode_split_by_hand():
    """A batch request: 3 prompt positions in one prefill pass, then 2
    decode inputs at contexts 4 and 5."""
    cfg = tiny_cfg()
    kvb = work.kv_bytes_per_position(cfg)
    r = dict(hist=0, prompt=[7, 8, 9], fed=5, prefilled=3)
    flops = {"flops_bf16": 1.0, "hbm_bytes_per_s": 1e30}
    nbytes = {"flops_bf16": 1e30, "hbm_bytes_per_s": 1.0}
    per_layer_head = 4 * 4 * 16              # 4 x heads x head_dim
    assert work.least_prefill_attention_seconds(cfg, [r], flops) == \
        2 * per_layer_head * (1 + 2 + 3)
    # q, k, v and out of 3 positions: (2 x 4 + 2 x 2) x 16 x 2 bytes a layer
    assert work.least_prefill_attention_seconds(cfg, [r], nbytes) == \
        2 * 3 * (2 * 4 + 2 * 2) * 16 * 2
    assert work.least_decode_attention_seconds(cfg, [r], flops) == \
        2 * per_layer_head * (4 + 5)
    assert work.least_decode_attention_seconds(cfg, [r], nbytes) == \
        kvb * (4 + 5)


@pytest.mark.parametrize("tie", [True, False])
def test_weights_layer_by_layer_equal_the_whole_tree(tie):
    cfg = tiny_cfg(tie_word_embeddings=tie, num_hidden_layers=3)
    whole = weights.make(cfg, SEED)
    for i in range(3):
        one = weights.layer(cfg, SEED, i)
        same = jax.tree_util.tree_map(lambda a, b: bool((a[i] == b).all()),
                                      whole["layers"], one)
        assert all(jax.tree_util.tree_leaves(same))
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                                  whole["embed"], weights.embed(cfg, SEED))
    assert all(jax.tree_util.tree_leaves(same))
    other = weights.make(cfg, SEED + 1)
    assert not bool((other["layers"]["mixer"]["wq"]
                     == whole["layers"]["mixer"]["wq"]).all())


@pytest.mark.parametrize("tie", [True, False])
def test_reference_agrees_with_the_program_in_float32(tie):
    """An independent witness: the program's own full forward, run in
    float32 on the same weights, puts first what the reference puts first,
    with the same logits."""
    from repro.models import build_model
    cfg = tiny_cfg(tie_word_embeddings=tie)
    mc = dataclasses.replace(spec.model_config(cfg),
                             param_dtype=jnp.float32,
                             compute_dtype=jnp.float32)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    weights.make(cfg, SEED))
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg["vocab_size"], (2, 128)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = build_model(mc).forward_train(params,
                                               {"tokens": jnp.asarray(tokens)})
    targets = np.asarray(logits.argmax(-1), np.int32)
    gaps, low = reference.gaps(cfg, SEED, tokens, targets, control=True)
    assert np.nanmax(gaps) < 1e-4
    assert np.nanmax(low) > 100 * max(np.nanmax(gaps), 1e-6)


def test_served_tokens_against_the_reference():
    """The engine at the cell's slot count, greedy, bf16, fed each sent
    and served token once (``calibrate.without_repeat``): every served
    token of every turn lies within rounding of the reference's best over
    the sessions as sent and served."""
    cfg = tiny_cfg()
    mix = dict(spec.load_json(spec.HERE / "traffic" / "long-answers.json"),
               **TINY_MIX)
    with calibrate.without_repeat():
        eng, openings = sessions.build_engine(cfg, mix, SEED, jax.devices())
        sched = traffic.schedule(mix, SEED, 4.0, cfg["vocab_size"],
                                 cfg["serving"]["max_seq"] - sessions.OPENING)
        recs = []
        for t in sched:
            out, _ = eng.turn(t.sid, t.prompt, gen_tokens=t.gen)
            recs.append(dict(sid=t.sid, prompt=t.prompt, gen=t.gen,
                             out=[int(x) for x in out], done=1.0))
    tokens, targets, malformed = sessions.sent_sequences(
        recs, openings, cfg["serving"]["max_seq"], cfg["vocab_size"])
    assert malformed == 0
    gaps, _ = reference.gaps(cfg, SEED, tokens, targets)
    assert np.isfinite(gaps).sum() == sum(len(r["out"]) for r in recs)
    served = gaps[np.isfinite(gaps)]
    assert served.max() < 0.1
    assert np.mean(served == 0) > 0.9


def test_sent_sequences_hold_what_was_sent_and_served():
    """Prompts and answers follow each session's set-up turn in order;
    each answer token is the target at the position before it; an answer
    of the wrong length is malformed and not compared."""
    openings = {"s0": [5, 6], "s1": [7, 8]}
    recs = [dict(sid="s1", prompt=[1, 2], gen=2, out=[3, 4], done=1.0),
            dict(sid="s0", prompt=[9], gen=3, out=[10, 11], done=1.0),
            dict(sid="s1", prompt=[12], gen=1, out=[13], done=1.0),
            dict(sid="s0", prompt=[14], gen=1, out=None, done=None)]
    tokens, targets, malformed = sessions.sent_sequences(recs, openings,
                                                         10, 20)
    assert malformed == 1
    assert tokens.tolist() == [[5, 6, 9, 10, 11, 0, 0, 0, 0, 0],
                               [7, 8, 1, 2, 3, 4, 12, 13, 0, 0]]
    assert targets.tolist() == [[-1] * 10,
                                [-1, -1, -1, 3, 4, -1, 13, -1, -1, -1]]


def test_batch_sequences_and_sample():
    """A request's prompt then answer; answer token ``i`` is the target at
    the position before it; the sample is drawn from the seed, holds the
    longest request, and a malformed answer is not compared."""
    recs = [dict(prompt=[1, 2, 3], out=[4, 5], gen=2, done=1.0),
            dict(prompt=[6, 7, 8], out=[9, 300], gen=2, done=1.0),
            dict(prompt=[1, 1, 1], out=[2, 2, 2], gen=3, done=1.0)]
    tokens, targets, malformed = batch.sequences(recs[:2], vocab=256)
    assert malformed == 1
    assert tokens.tolist() == [[1, 2, 3, 4, 5], [6, 7, 8, 9, 300]]
    assert targets.tolist() == [[-1, -1, 4, 5, -1], [-1] * 5]
    many = [dict(r, prompt=[k]) for k in range(20) for r in recs[:1]]
    many[13]["out"] = [4, 5, 6]
    for seed in (1, 2 ** 40):
        got = batch.sample(many, 4, seed)
        assert len(got) == 4 and got[0] is many[13]
        assert got == batch.sample(many, 4, seed)
    assert batch.sample(many, 4, 1) != batch.sample(many, 4, 2)


def test_batch_prompts_are_the_seeds_own():
    mix = spec.load_json(spec.HERE / "traffic" / "batch-answers.json")
    a = traffic.batch_prompts(mix, 2 ** 33 + 1, 0, 1000)
    assert a.shape == (mix["batch"], mix["prompt"])
    assert a.min() >= 1 and a.max() < 1000
    assert (a == traffic.batch_prompts(mix, 2 ** 33 + 1, 0, 1000)).all()
    assert not (a == traffic.batch_prompts(mix, 2 ** 33 + 1, 1, 1000)).all()
    assert not (a == traffic.batch_prompts(mix, 2 ** 33 + 2, 0, 1000)).all()
