"""Traffic kind ``sessions``: open-loop multi-turn sessions through the
program's ``ServingEngine``.

Set-up makes the weights on the device from the seed, builds the engine
with the cell's rows, slots and routing, and opens each session with one
set-up turn (one token in, one out).  That turn compiles every program the
window will run, on the engine and the rows that run them; sessions have
no close call, so a throwaway warm-up engine would hold slots or compile
for nothing.

The window: ``traffic.schedule`` gives each turn a due time.  One thread
sleeps until a turn is due, calls ``ServingEngine.turn`` and waits for the
rows' state (``block_until_ready``).  A turn's latency runs from its due
time to that return, so queueing behind earlier turns counts.  Turns still
undone ``drain_seconds`` after the window closes count as failed.

The check runs the reference over each session's tokens as sent and
served (``sent_sequences``), never over what the engine fed itself.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from . import spec, traffic

SPAN = "bench.turn"      # host annotation around one unit of service
OPENING = 2              # positions a session's set-up turn adds


def _sync(eng) -> None:
    import jax
    jax.block_until_ready([(r.cache, r.lengths) for r in eng.rows])


def build_engine(cfg: dict, mix: dict, seed: int, devices):
    """Weights from ``seed`` in one jitted call, the engine, and one set-up
    turn per session (one token in, one out).  Returns the engine and each
    session's set-up token and answer, by session id."""
    import jax
    from repro.models import build_model
    from repro.serving import ServingEngine
    from . import weights
    model = build_model(spec.model_config(cfg))
    params = weights.make(cfg, seed, devices[0])
    eng = ServingEngine(model, params, n_rows=mix["rows"],
                        max_slots=cfg["serving"]["slots_per_row"],
                        max_seq=cfg["serving"]["max_seq"],
                        policy=mix["routing"], devices=devices)
    del params
    rng = np.random.default_rng([seed, 1])
    openings = {}
    for sid in traffic.session_ids(mix):
        eng.open_session(sid)
        tok = int(rng.integers(1, cfg["vocab_size"]))
        out, _ = eng.turn(sid, [tok], gen_tokens=1)
        openings[sid] = [tok] + [int(x) for x in out]
    _sync(eng)
    jax.block_until_ready(eng.rows[0].params)
    return eng, openings


def serve_window(eng, sched: List[traffic.Turn], seconds: float,
                 drain: float, annotate):
    """Drive ``eng`` through ``sched``; returns (turn records, window
    facts)."""
    records = [dict(sid=t.sid, due=t.due, prompt=t.prompt, gen=t.gen,
                    start=None, done=None) for t in sched]
    held = {}                          # positions each session holds
    t0 = time.perf_counter()
    prev_done, late = 0.0, []
    for r in records:
        now = time.perf_counter() - t0
        if now > seconds + drain:
            break
        if r["due"] > now:
            with annotate("bench.idle"):
                time.sleep(r["due"] - now)
        start = time.perf_counter() - t0
        late.append(start - max(r["due"], prev_done))
        with annotate(SPAN, sid=r["sid"]):
            out, m = eng.turn(r["sid"], r["prompt"], gen_tokens=r["gen"],
                              now=r["due"])
            _sync(eng)
        prev_done = time.perf_counter() - t0
        r.update(start=start, done=prev_done, out=[int(x) for x in out],
                 row=m.row, migrated=m.migrated,
                 migration_bytes=m.migration_bytes, tokens=m.tokens)
        # positions the session held before the turn, and those the turn
        # adds: its prompt and its answer
        r["hist"] = held.get(r["sid"], OPENING)
        r["fed"] = len(r["prompt"]) + len(r["out"])
        held[r["sid"]] = r["hist"] + r["fed"]
    end = time.perf_counter() - t0
    served = [r for r in records if r["done"] is not None]
    return records, dict(
        window_s=end,
        in_service_s=sum(r["done"] - r["start"] for r in served),
        late_max_s=max(late) if late else 0.0,
        late_mean_s=float(np.mean(late)) if late else 0.0)


def sent_sequences(records: List[dict], openings: Dict[str, list],
                   max_seq: int, vocab: int):
    """Each session's tokens as sent and served, the targets to compare,
    and the count of malformed answers.

    A session holds its set-up turn's token and answer, then, for every
    served turn, the turn's prompt followed by its answer: what the user
    sent and what the engine returned, and nothing that the engine fed of
    its own.  Answer token ``i`` is the target at the position before it.
    An answer of the wrong length, or with an id outside the vocabulary,
    is malformed and not compared.  Returns (tokens, targets, malformed),
    the first two (sessions, ``max_seq``) with -1 where nothing was served.
    """
    sids = sorted(openings)
    seqs = {sid: list(openings[sid]) for sid in sids}
    targets = np.full((len(sids), max_seq), -1, np.int32)
    malformed = 0
    for r in records:
        if r["done"] is None:
            continue
        seq, out = seqs[r["sid"]], r["out"]
        seq.extend(r["prompt"])
        if len(out) == r["gen"] and all(0 <= x < vocab for x in out):
            b = sids.index(r["sid"])
            targets[b, len(seq) - 1:len(seq) - 1 + len(out)] = out
        else:
            malformed += 1
        seq.extend(out)
    tokens = np.zeros((len(sids), max_seq), np.int32)
    for b, sid in enumerate(sids):
        seq = seqs[sid][:max_seq]       # a malformed answer may run over
        tokens[b, :len(seq)] = seq
    return tokens, targets, malformed


# -- the kind's interface to ``harness.run_cell`` ------------------------------

def prepare(cfg: dict, mix: dict, seed: int, devices):
    """(program state, what the check keeps once the program is freed)."""
    eng, openings = build_engine(cfg, mix, seed, devices)
    return eng, openings


def serve(eng, cfg: dict, mix: dict, seed: int, seconds: float, annotate):
    sched = traffic.schedule(mix, seed, seconds, cfg["vocab_size"],
                             cfg["serving"]["max_seq"] - OPENING)
    records, facts = serve_window(eng, sched, seconds,
                                  mix["drain_seconds"], annotate)
    facts["describe"] = (
        f"{len(records)} turns due at {mix['rate_turns_per_s']} turns/s; "
        f"generator late by at most {facts['late_max_s'] * 1e3:.3f} ms "
        f"(mean {facts['late_mean_s'] * 1e3:.3f} ms)")
    return records, facts


def compared(records: List[dict], openings, cfg: dict, mix: dict,
             seed: int):
    """Every token served in the window, over the sessions as sent."""
    return sent_sequences(records, openings, cfg["serving"]["max_seq"],
                          cfg["vocab_size"])


def end_to_end(records: List[dict], facts: dict, seconds: float,
               mix: dict) -> dict:
    """Latency from due time to done (a failed turn counts as a miss at
    the drain's end), and answer tokens served per second of the window."""
    limit_s = seconds + mix["drain_seconds"]
    lat = [(r["done"] if r["done"] is not None else limit_s) - r["due"]
           for r in records]
    served = sum(len(r["out"]) for r in records if r["done"] is not None)
    return {"turn_p50_s": float(np.percentile(lat, 50)),
            "tokens_per_s": served / facts["window_s"]}
