"""``correct`` at a CPU test size: a sound run passes; the same run with
its timed path broken underneath fails; and the fp8 control, judged by
the run's own verdict, fails where the program passes.

The harness's look for a chip is skipped; everything after it runs as in
a benchmark run.  The ``sessions`` cell's served path is run under
``calibrate.without_repeat`` (``ServingEngine.turn`` as it stands feeds a
prompt's last token twice, so a run of it reads not correct).  Limits here
are the test size's own, set from readings at that size (sound runs of the
batch cell read up to 0.029 on six seeds, the fp8 control 0.29 and up; the
sessions witness up to 0.024); the cells' limits are in ``limits/``.
"""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import calibrate, harness
from conftest import BATCH_CELL, SESSIONS_CELL, tiny_cell

SESSIONS = SESSIONS_CELL["name"]
TEST_LIMIT = {BATCH_CELL: 0.07, SESSIONS: 0.03}


def run(name, seed=2 ** 31 + 5):
    c = tiny_cell(name)
    c["limits"] = {"max_logit_gap": TEST_LIMIT[name]}
    with calibrate.without_repeat():
        return harness.run_cell(name, seed, 3.0, False, require_tpu=False,
                                cell=c, log=lambda *a: None)


@pytest.mark.parametrize("name", [BATCH_CELL, SESSIONS])
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "compared"
    if name == SESSIONS:
        assert out["attempted"] == 9


# -- faults planted in the batch cell's serve step ------------------------------

def _broken_serve_step(monkeypatch, fault):
    """The program's serve step, built as the program builds it, with
    ``fault(tokens, lengths, cache, next_tok, new_cache)`` applied to what
    it returns."""
    from repro.launch import steps
    orig = steps.make_serve_step

    def make(*a, **k):
        bundle = orig(*a, **k)
        fn = bundle.fn

        def broken(params, cache, tokens, lengths):
            nt, nc = fn(params, cache, tokens, lengths)
            return fault(tokens, lengths, cache, nt, nc)

        bundle.fn = broken
        return bundle

    monkeypatch.setattr(steps, "make_serve_step", make)


def _token_altered(tokens, lengths, cache, nt, nc):
    # the third decode step of every batch, every request
    prompt = tiny_cell(BATCH_CELL)["traffic"]["prompt"]
    return jnp.where(lengths == prompt + 2, (nt + 1) % 256, nt), nc


def _state_unchanged(tokens, lengths, cache, nt, nc):
    return nt, cache


def _half_the_batch_left_out(tokens, lengths, cache, nt, nc):
    half = tokens.shape[0] // 2
    return (nt.at[half:].set(tokens[half:]),
            jax.tree_util.tree_map(lambda n, o: n.at[:, half:].set(
                o[:, half:]), nc, cache))


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_the_batch_left_out])
def test_batch_fault_is_not_correct(monkeypatch, fault):
    _broken_serve_step(monkeypatch, fault)
    out = run(BATCH_CELL)
    assert not out["correct"]
    assert out["compared"]["max_logit_gap"]["value"] > TEST_LIMIT[BATCH_CELL]


# -- faults planted in the sessions cell's engine -------------------------------

def test_token_altered_where_produced(monkeypatch):
    from repro.serving import ServingEngine
    orig = ServingEngine._decode_one
    calls = []

    def altered(self, row, slot, tok, adapter):
        nxt, cache, lengths = orig(self, row, slot, tok, adapter)
        calls.append(1)
        if len(calls) == 40:           # one token, inside the window
            nxt = (nxt + 1) % self.model.cfg.vocab_size
        return nxt, cache, lengths

    monkeypatch.setattr(ServingEngine, "_decode_one", altered)
    out = run(SESSIONS)
    assert not out["correct"]
    assert out["compared"]["max_logit_gap"]["value"] > TEST_LIMIT[SESSIONS]


def test_step_returns_its_state_unchanged(monkeypatch):
    from repro.serving import ServingEngine
    monkeypatch.setattr(ServingEngine, "_commit",
                        staticmethod(lambda old, new, mask: old))
    out = run(SESSIONS)
    assert not out["correct"]
    assert out["compared"]["max_logit_gap"]["value"] > TEST_LIMIT[SESSIONS]


@pytest.mark.parametrize("name", [BATCH_CELL, SESSIONS])
def test_fp8_control_is_not_correct(name):
    """The reference computed in fp8, in the program's place, fails the
    run's own verdict on every seed, at three times or more the widest gap
    of a sound run (for the sessions cell, the served path fed each token
    once), which passes."""
    c = tiny_cell(name)
    c["limits"] = {"max_logit_gap": TEST_LIMIT[name]}
    compiles = harness.CompileCounter()
    sound, seconds = ("witness", 3.0) if name == SESSIONS else ("program", 0)
    got = [calibrate.readings(c, seed, seconds, jax.devices(), compiles)
           for seed in (11, 2 ** 31 + 12, 2 ** 32 + 13)]
    for r in got:
        assert r[sound]["correct"] and not r["control"]["correct"], r
    widest = max(r[sound]["max_gap"] for r in got)
    control = min(r["control"]["max_gap"] for r in got)
    assert control >= 3 * widest, got
