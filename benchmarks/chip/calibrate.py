#!/usr/bin/env python3
"""Readings that set a cell's rate and its limit for ``correct``; not part
of a benchmark run.  Run on the chip, from the root of a checkout.

    python3 benchmarks/chip/calibrate.py sweep --workload W --seed N \\
        --seconds S --rates 0.2,0.3,0.4
        A ``sessions`` cell: one window per rate (a fresh engine each),
        printing latency, how far past the window the last turn ended, and
        the busy share: the knee is the highest rate whose backlog does
        not grow.

    python3 benchmarks/chip/calibrate.py control --workload W \\
        --seeds a,b,c --seconds S
        For each seed, serves the cell's traffic as a run does (a
        ``sessions`` cell's schedule back to back, without waits; a
        ``batch`` cell's batches for ``S`` seconds, at least one) and
        reads, under the float32 reference over the tokens that a run
        compares, the widest logit gap of the program's served tokens and
        of the tokens that the fp8 control puts first at the same
        positions; for a ``sessions`` cell also of the tokens served by
        the program fed each sent and served token once
        (``without_repeat``, a witness).  Each goes through the run's own
        ``harness.verdict``, so each prints the ``correct`` a run would.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@contextlib.contextmanager
def without_repeat():
    """The program's served path, fed each sent and served token once.

    ``ServingEngine.turn`` feeds the whole prompt, starts decoding by
    feeding the prompt's last token again at the next position, and never
    feeds the answer's last token.  Inside this context each turn is first
    given the session's previous answer token that was never fed, and
    where a turn's first decode input repeats the token that the prefill
    fed last, the slot's length is stepped back by one, so that the token
    is fed again at its own position.  The cache then holds the session as
    sent and served, and the program's own step answers it: a witness for
    the reference, never part of a run.  Use one context per engine.
    """
    from repro.serving import ServingEngine
    turn, advance = ServingEngine.turn, ServingEngine._advance
    decode = ServingEngine._decode_one
    unfed, last = {}, {}

    @functools.wraps(turn)
    def turn_(self, sid, prompt, gen_tokens=16, **kw):
        lead = [unfed.pop(sid)] if sid in unfed else []
        out, m = turn(self, sid, lead + list(prompt), gen_tokens, **kw)
        if out:
            unfed[sid] = int(out[-1])
        return out, m

    @functools.wraps(advance)
    def advance_(self, row, slot, tok):
        last[id(row), slot] = int(tok)
        return advance(self, row, slot, tok)

    @functools.wraps(decode)
    def decode_(self, row, slot, tok, adapter):
        if last.pop((id(row), slot), None) == int(tok):
            row.lengths = row.lengths.at[slot].add(-1)
        return decode(self, row, slot, tok, adapter)

    saved = (turn, advance, decode)
    (ServingEngine.turn, ServingEngine._advance,
     ServingEngine._decode_one) = (turn_, advance_, decode_)
    try:
        yield
    finally:
        (ServingEngine.turn, ServingEngine._advance,
         ServingEngine._decode_one) = saved


NOTHING = lambda *a, **k: contextlib.nullcontext()      # no annotations


def serve_as_a_run(cfg, mix, seed, seconds, devices, compiles):
    """The cell's traffic for ``seed`` (a ``sessions`` mix with every turn
    due at once); returns the tokens, targets and malformed count that a
    run would compare, the requests failed and the compiles inside."""
    from benchmarks.chip import harness, sessions, traffic
    kind = harness.KINDS[mix["kind"]]
    program, kept = kind.prepare(cfg, mix, seed, devices)
    c0 = compiles.n
    if kind is sessions:
        sched = traffic.schedule(mix, seed, seconds, cfg["vocab_size"],
                                 cfg["serving"]["max_seq"] - sessions.OPENING)
        for t in sched:
            t.due = 0.0
        recs, _ = sessions.serve_window(program, sched, seconds,
                                        mix["drain_seconds"], NOTHING)
    else:
        recs, _ = kind.serve(program, cfg, mix, seed, seconds, NOTHING)
    n_comp = compiles.n - c0
    del program
    gc.collect()
    failed = sum(r["done"] is None for r in recs)
    return kind.compared(recs, kept, cfg, mix, seed) + (failed, n_comp)


def readings(cell, seed, seconds, devices, compiles, witness=True):
    """The pickers' widest gaps and verdicts for one seed."""
    import numpy as np
    from benchmarks.chip import harness, reference
    cfg, mix = cell["config"], cell["traffic"]
    out = {"seed": seed}
    t0 = time.perf_counter()
    tokens, targets, bad, failed, n_comp = serve_as_a_run(
        cfg, mix, seed, seconds, devices, compiles)
    t1 = time.perf_counter()
    served, low = reference.gaps(cfg, seed, tokens, targets, control=True)
    out.update(sequences=int(tokens.shape[0]),
               compared=int(np.isfinite(served).sum()),
               serve_s=t1 - t0, reference_s=time.perf_counter() - t1)
    pickers = [("program", served), ("control", low)]
    if witness and mix["kind"] == "sessions":
        with without_repeat():
            w_tokens, w_targets = serve_as_a_run(cfg, mix, seed, seconds,
                                                 devices, compiles)[:2]
        pickers.append(("witness",
                        reference.gaps(cfg, seed, w_tokens, w_targets)[0]))
    for name, gaps in pickers:
        ok = np.isfinite(gaps)
        checks, correct = harness.verdict(
            cell["limits"], harness.widest_gap(gaps), bad, failed, n_comp)
        out[name] = {"correct": correct,
                     "max_gap": checks["max_logit_gap"]["value"],
                     "p99_gap": float(np.nanpercentile(gaps, 99)),
                     "first_differs": int((gaps[ok] > 0).sum())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--config", help="with --traffic: a cell that "
                    "BENCHMARK.json does not list, by its pieces' names")
    ap.add_argument("--traffic")
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from benchmarks.chip import harness, sessions, spec, traffic

    if args.config:
        cell = spec.assemble(
            {"name": args.workload, "config": args.config,
             "traffic": args.traffic, "chips": 1},
            spec.load_json(ROOT / "BENCHMARK.json"), ROOT)
    else:
        cell = spec.cell(args.workload, ROOT)
    cfg, mix = cell["config"], cell["traffic"]
    devices = harness.devices_for(cell["workload"]["chips"], True)
    harness.enable_compile_cache(ROOT)
    compiles = harness.CompileCounter()
    max_seq = cfg["serving"]["max_seq"]

    if args.mode == "sweep":
        for rate in [float(r) for r in args.rates.split(",")]:
            m = dict(mix, rate_turns_per_s=rate)
            eng, _ = sessions.build_engine(cfg, m, args.seed, devices)
            c0 = compiles.n
            sched = traffic.schedule(m, args.seed, args.seconds,
                                     cfg["vocab_size"],
                                     max_seq - sessions.OPENING)
            recs, facts = sessions.serve_window(eng, sched, args.seconds,
                                                m["drain_seconds"], NOTHING)
            del eng
            gc.collect()
            lat = [r["done"] - r["due"] for r in recs if r["done"]]
            busy = sum(r["done"] - r["start"] for r in recs if r["done"])
            print(json.dumps({
                "rate": rate, "turns": len(recs), "served": len(lat),
                "p50_s": harness.percentile(lat, 50),
                "p95_s": harness.percentile(lat, 95), "max_s": max(lat),
                "backlog_s": facts["window_s"] - args.seconds,
                "busy_share": busy / facts["window_s"],
                "service_mean_s": busy / len(lat),
                "fed_per_turn": float(np.mean([r["fed"] for r in recs
                                               if r["done"]])),
                "compiles_in_window": compiles.n - c0}), flush=True)
        return 0

    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(readings(cell, seed, args.seconds, devices,
                                  compiles)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
