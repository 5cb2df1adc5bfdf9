"""Serving engine: session/KV affinity (paper §7.2 applied)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import build_model
from repro.serving import ServingEngine, make_adapter


@pytest.fixture(scope="module")
def model_and_params():
    cfg = configs.get_smoke("granite-3-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def drive(engine, n_sessions=6, turns=3, gen=4):
    for i in range(n_sessions):
        engine.open_session(f"s{i}")
    t = 0.0
    outs = {}
    for turn in range(turns):
        for i in range(n_sessions):
            out, _ = engine.turn(f"s{i}", [1 + i, 2, 3], gen_tokens=gen,
                                 now=t)
            outs.setdefault(f"s{i}", []).extend(out)
            t += 0.001
    return outs


def test_affinity_policy_never_migrates(model_and_params):
    cfg, model, params = model_and_params
    eng = ServingEngine(model, params, n_rows=3, max_slots=4, max_seq=64,
                        policy="affinity")
    drive(eng)
    s = eng.summary()
    assert s["migrations"] == 0
    assert s["migration_bytes"] == 0


def test_random_policy_migrates_and_costs(model_and_params):
    cfg, model, params = model_and_params
    eng = ServingEngine(model, params, n_rows=3, max_slots=6, max_seq=64,
                        policy="random")
    drive(eng)
    s = eng.summary()
    assert s["migrations"] > 0
    assert s["migration_bytes"] > 0


def test_affinity_ttft_wins_when_state_is_expensive(model_and_params):
    """Production regime: a session's KV state is large relative to a
    decode step (GBs on real models), so any migration dominates TTFT.
    Modeled here by a slow interconnect; the smoke model's state is tiny,
    production caches are ~10^5x bigger."""
    from repro.runtime.simulation import NetProfile
    slow = NetProfile(bandwidth=1e6, rtt=0.25)
    cfg, model, params = model_and_params
    ea = ServingEngine(model, params, n_rows=3, max_slots=6, max_seq=64,
                       policy="affinity", net=slow)
    er = ServingEngine(model, params, n_rows=3, max_slots=6, max_seq=64,
                       policy="random", seed=1, net=slow)
    drive(ea)
    drive(er)
    assert ea.summary()["ttft_mean"] <= er.summary()["ttft_mean"]


def test_migration_preserves_generation(model_and_params):
    """Greedy decode must produce identical tokens regardless of routing —
    migrations move state, they must not change it."""
    cfg, model, params = model_and_params
    ea = ServingEngine(model, params, n_rows=3, max_slots=6, max_seq=64,
                       policy="affinity")
    er = ServingEngine(model, params, n_rows=3, max_slots=6, max_seq=64,
                       policy="random", seed=3)
    oa = drive(ea, n_sessions=4, turns=2)
    orr = drive(er, n_sessions=4, turns=2)
    assert oa == orr


def test_adapter_changes_logits(model_and_params):
    cfg, model, params = model_and_params
    eng = ServingEngine(model, params, n_rows=2, max_slots=4, max_seq=64,
                        policy="affinity")
    ad = make_adapter(jax.random.PRNGKey(1), "a1", cfg.d_model,
                      cfg.vocab_size)
    # standard LoRA init has B=0 (no-op); randomize B to make it active
    ad.B = jax.random.normal(jax.random.PRNGKey(2), ad.B.shape) * 2.0
    eng.adapters.register(ad)
    eng.open_session("plain")
    eng.open_session("tuned", adapter="a1")
    out_plain, _ = eng.turn("plain", [1, 2, 3], gen_tokens=6)
    out_tuned, _ = eng.turn("tuned", [1, 2, 3], gen_tokens=6)
    assert out_plain != out_tuned


def test_adapter_affinity_fetches_once(model_and_params):
    cfg, model, params = model_and_params
    eng = ServingEngine(model, params, n_rows=4, max_slots=8, max_seq=64,
                        policy="adapter_affinity")
    ad = make_adapter(jax.random.PRNGKey(1), "a1", cfg.d_model,
                      cfg.vocab_size)
    eng.adapters.register(ad)
    for i in range(6):
        eng.open_session(f"s{i}", adapter="a1")
    drive_sessions = [f"s{i}" for i in range(6)]
    for sid in drive_sessions:
        eng.turn(sid, [1, 2], gen_tokens=2)
    # all sessions share the adapter's affinity key -> one row, one fetch
    assert eng.adapters.fetches == 1


def _same_row_sids(router, k):
    """First ``k`` session ids the affinity policy homes on one row."""
    from repro.serving.sessions import Session
    buckets = {}
    for i in range(200):
        sid = f"sess{i}"
        r = router.route(Session(sid=sid), f"{sid}:0")
        buckets.setdefault(r, []).append(sid)
        if len(buckets[r]) == k:
            return r, buckets[r]
    raise AssertionError("no row collected k sessions")


def test_row_overflow_spills_to_best_free_row(model_and_params):
    """The row scheduler's overflow-spill path: a session whose affinity
    row is full must land on the best-signal row WITH a free slot instead
    of asserting on the full one."""
    cfg, model, params = model_and_params
    eng = ServingEngine(model, params, n_rows=2, max_slots=2, max_seq=64,
                        policy="affinity")
    home, sids = _same_row_sids(eng.router, 3)
    # occupy both slots of the affinity row
    for sid in sids[:2]:
        eng.open_session(sid)
        _, m = eng.turn(sid, [1], gen_tokens=1)
        assert m.row == home
    eng.open_session(sids[2])                   # same home row, now full
    _, m2 = eng.turn(sids[2], [1], gen_tokens=1)
    assert m2.row != home                       # spilled, not crashed
    assert eng.rows[m2.row].load() == 1


def test_row_overflow_spill_prefers_emptier_row(model_and_params):
    """With several spill candidates, the row scheduler's (free-lane,
    backlog, load) signal picks the least-loaded one."""
    cfg, model, params = model_and_params
    eng = ServingEngine(model, params, n_rows=3, max_slots=2, max_seq=64,
                        policy="affinity")
    home, sids = _same_row_sids(eng.router, 3)
    for sid in sids[:2]:
        eng.open_session(sid)
        eng.turn(sid, [1], gen_tokens=1)
    # make one non-home row busier than the other
    others = [i for i in range(3) if i != home]
    eng.rows[others[0]].busy_until = 10.0
    eng.open_session(sids[2])
    _, m = eng.turn(sids[2], [1], gen_tokens=1, now=0.5)
    assert m.row == others[1]


def test_heterogeneous_rows_price_decode_by_tier(model_and_params):
    """A faster row profile yields cheaper virtual decode time; the
    uniform default stays byte-identical to the pre-tier engine."""
    from repro.runtime import GPU_H100, UNIFORM
    cfg, model, params = model_and_params
    base = ServingEngine(model, params, n_rows=2, max_slots=2, max_seq=64,
                         policy="affinity")
    fast = ServingEngine(model, params, n_rows=2, max_slots=2, max_seq=64,
                         policy="affinity",
                         row_profiles=[GPU_H100, GPU_H100])
    # calibration is per-engine; pin identical service times for fairness
    fast._svc = dict(base._svc)
    uni = ServingEngine(model, params, n_rows=2, max_slots=2, max_seq=64,
                        policy="affinity", row_profiles=[UNIFORM])
    uni._svc = dict(base._svc)
    for eng in (base, fast, uni):
        eng.open_session("s0")
    _, mb = base.turn("s0", [1, 2], gen_tokens=4)
    _, mf = fast.turn("s0", [1, 2], gen_tokens=4)
    _, mu = uni.turn("s0", [1, 2], gen_tokens=4)
    assert mf.decode_time < mb.decode_time      # 2x gpu speed
    assert mu.decode_time == mb.decode_time     # uniform == identity


def test_turn_traces_decompose_to_e2e(model_and_params):
    """Every traced turn's spans telescope exactly over its virtual
    window and the blame decomposition sums to the turn's e2e; random
    routing must surface migration spans carrying the moved bytes."""
    from repro.runtime import TraceRecorder
    from repro.workflows import decompose

    cfg, model, params = model_and_params
    rec = TraceRecorder()
    eng = ServingEngine(model, params, n_rows=3, max_slots=6, max_seq=64,
                        policy="random", tracer=rec)
    drive(eng)
    traces = rec.traces()
    assert rec.n_completed == len(eng.metrics) == len(traces) == 18
    totals = {}
    for tr in traces:
        sid, turn = tr.instance.split(":")
        assert sid in eng.sessions and turn.isdigit()
        parts = decompose(tr)
        assert abs(sum(parts.values()) - tr.e2e) < 1e-9
        spans = sorted(tr.spans, key=lambda sp: sp.t0)
        assert spans and {sp.cat for sp in spans} >= {"compute"}
        # telescoping: first span opens at submit, last closes at
        # complete, no span starts before its predecessor ends
        assert spans[0].t0 >= tr.t_submit - 1e-12
        assert spans[-1].t1 == pytest.approx(tr.t_complete, abs=1e-12)
        for a, b in zip(spans, spans[1:]):
            assert b.t0 >= a.t1 - 1e-12
        for c, v in parts.items():
            totals[c] = totals.get(c, 0.0) + v
    assert totals["compute"] > 0.0
    migrated = [tr for tr in traces
                if any(sp.cat == "migration" for sp in tr.spans)]
    assert migrated, "random routing should migrate at least one turn"
    for tr in migrated:
        sp = next(s for s in tr.spans if s.cat == "migration")
        assert sp.name == "session_migrate" and sp.args["bytes"] > 0
    assert totals["migration"] > 0.0


def test_one_row_per_device_serves_the_same_tokens():
    """One row per device (four virtual CPU devices, in a child process
    that owns them) serves the same tokens as all rows on one device, and
    random routing moves sessions across devices: the CPU rehearsal of
    ``chip_smoke.py --four-chips``."""
    root = Path(__file__).resolve().parents[1]
    code = textwrap.dedent("""
        import jax, chip_smoke
        from repro import configs
        assert len(jax.devices()) == 4, jax.devices()
        model, params = chip_smoke.build(configs.get_smoke("granite-3-2b"), 0)
        chip_smoke.four_chip_phase(model, params, jax.devices(), seed=0,
                                   max_seq=64, n_sessions=8, n_turns=2,
                                   prompt_lens=(4, 12))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]))
    run = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-4000:]
    assert "random: 16/16 turns token-identical" in run.stdout
