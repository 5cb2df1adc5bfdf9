#!/usr/bin/env python3
"""Smoke run of the served path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips, one serving row each

One chip: builds granite-3-2b at its published widths with random weights
from ``--seed``, serves a few multi-turn sessions through ``ServingEngine``
(affinity routing, Pallas kernels), then runs one full-width decode step
under the Pallas backend and under the jnp reference on the same inputs and
cache, and checks that they agree.

``--four-chips``: runs one session script with one row per chip, under
random routing (sessions migrate across chips) and under affinity routing,
and checks both token for token against the same script with all four rows
on one device.  No other phase runs.

Times printed are host wall-clock seconds taken after ``block_until_ready``;
the engine's own ``ttft`` is virtual time and is not printed.  The last line
of standard output is one JSON object naming the device.  The script exits
non-zero without that line if JAX finds no TPU or any phase fails.  Run it
from the root of a checkout: it imports the package under ``src/``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "granite-3-2b"
GEN_TOKENS = 16
BF16_TOL = 2e-2           # relative to the logits' scale (tests' bf16 tol)


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache hits do not count)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def snapshot(self):
        return self.n, self.seconds


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def make_script(rng, vocab: int, n_sessions: int, n_turns: int,
                prompt_lens: tuple):
    """[(turn, sid, prompt)] in serving order: every session's turn t
    before any session's turn t + 1."""
    lo, hi = prompt_lens
    return [(t, f"s{i}", [int(x) for x in rng.integers(
                1, vocab, int(rng.integers(lo, hi + 1)))])
            for t in range(n_turns) for i in range(n_sessions)]


def build(cfg, seed: int):
    import jax
    from repro.models import build_model
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return model, jax.block_until_ready(params)


def open_sessions(eng, sids, *, seed: int) -> None:
    """Open ``sids``; the first one decodes through a LoRA adapter, so the
    engine's hidden-state decode step runs too."""
    import jax
    from repro.serving import make_adapter
    cfg = eng.model.cfg
    eng.adapters.register(make_adapter(jax.random.PRNGKey(seed + 1),
                                       "support-bot", cfg.d_model,
                                       cfg.vocab_size))
    for i, sid in enumerate(sids):
        eng.open_session(sid, adapter="support-bot" if i == 0 else None)


def sync_rows(eng) -> None:
    import jax
    jax.block_until_ready([(r.cache, r.lengths) for r in eng.rows])


def serve_phase(model, params, *, seed: int, n_rows: int, slots: int,
                max_seq: int, n_sessions: int, n_turns: int,
                prompt_lens: tuple, compiles: CompileCounter):
    """Serve a multi-turn script through the engine; returns the engine."""
    import numpy as np
    from repro.serving import ServingEngine

    cfg = model.cfg
    c0 = compiles.snapshot()
    t0 = time.perf_counter()
    eng = ServingEngine(model, params, n_rows=n_rows, max_slots=slots,
                        max_seq=max_seq, policy="affinity")
    sync_rows(eng)
    c1 = compiles.snapshot()
    print(f"engine build (caches + decode_step compile + calibration): "
          f"{time.perf_counter() - t0:.3f} s host wall; "
          f"{c1[0] - c0[0]} compiles, {c1[1] - c0[1]:.3f} s compiling")
    open_sessions(eng, [f"s{i}" for i in range(n_sessions)], seed=seed)

    script = make_script(np.random.default_rng(seed), cfg.vocab_size,
                         n_sessions, n_turns, prompt_lens)
    steady = []
    for t, sid, prompt in script:
        c0 = compiles.snapshot()
        t0 = time.perf_counter()
        out, m = eng.turn(sid, prompt, gen_tokens=GEN_TOKENS)
        sync_rows(eng)
        wall = time.perf_counter() - t0
        c1 = compiles.snapshot()
        n_comp = c1[0] - c0[0]
        check(len(out) == GEN_TOKENS, f"{sid} turn {t} made {len(out)} tokens")
        check(all(0 <= x < cfg.vocab_size for x in out),
              f"{sid} turn {t} token out of vocabulary")
        print(f"turn {t} {sid}: row {m.row} prompt {len(prompt)} "
              f"gen {len(out)}: {wall:.4f} s host wall"
              + (f" (incl. {n_comp} compiles, {c1[1] - c0[1]:.3f} s)"
                 if n_comp else ""))
        if t > 0 and n_comp == 0:
            steady.append((wall, len(prompt) + len(out)))
    walls = [w for w, _ in steady]
    steps = sum(n for _, n in steady)
    check(bool(walls), "no steady turn: every turn compiled")
    print(f"steady turns (after the first round, none compiling): "
          f"n={len(walls)} "
          f"median {statistics.median(walls):.4f} s, "
          f"min {min(walls):.4f} s, max {max(walls):.4f} s host wall; "
          f"{sum(walls) / steps * 1e3:.3f} ms host wall per decode_step "
          f"launch ({steps} launches: prompt tokens + generated tokens)")
    return eng


def kernel_check(model, params, cache, lengths, *, seed: int):
    """One decode step under the platform's kernel backend vs the jnp
    reference, on the same inputs and cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    backend = ops.get_backend()
    check(backend != "jnp", "kernel backend is the jnp reference")
    B = lengths.shape[0]
    tokens = jnp.asarray(np.random.default_rng(seed + 2).integers(
        1, model.cfg.vocab_size, B), jnp.int32)
    compiled = jax.jit(lambda p, c, t, n: model.decode_step(p, c, t, n)[0]
                       ).lower(params, cache, tokens, lengths).compile()
    check(backend != "pallas" or "tpu_custom_call" in compiled.as_text(),
          "the pallas decode step holds no Pallas kernel")
    got = compiled(params, cache, tokens, lengths)
    with ops.backend("jnp"):
        want = jax.jit(lambda p, c, t, n: model.decode_step(p, c, t, n)[0])(
            params, cache, tokens, lengths)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    check(got.shape == (B, model.cfg.vocab_size), f"logits shape {got.shape}")
    check(bool(np.isfinite(got).all()), "non-finite logits (kernel)")
    check(bool(np.isfinite(want).all()), "non-finite logits (reference)")
    diff = float(np.abs(got - want).max())
    scale = max(1.0, float(np.abs(want).max()))
    same = got.argmax(-1) == want.argmax(-1)
    top2 = np.sort(want, axis=-1)[:, -2:]
    print(f"kernel check ({backend} vs jnp, one decode_step, "
          f"lengths {np.asarray(lengths).tolist()}): max |logit diff| "
          f"{diff:.6g}, logit scale {scale:.6g}, bound {BF16_TOL * scale:.6g};"
          f" greedy tokens equal {int(same.sum())}/{B}; reference top-1 "
          f"margins {np.round(top2[:, 1] - top2[:, 0], 5).tolist()}")
    check(diff <= BF16_TOL * scale, "kernel logits outside bf16 tolerance")
    check(bool(same.all()), "greedy tokens differ")
    return diff


def run_rows(model, params, devices, policy: str, script, *, seed: int,
             max_seq: int):
    """Serve ``script`` on 4 rows over ``devices``; returns the tokens and
    how many turns moved a session to a row on another device."""
    from repro.serving import ServingEngine
    eng = ServingEngine(model, params, n_rows=4, max_slots=8,
                        max_seq=max_seq, policy=policy, devices=devices)
    open_sessions(eng, sorted({sid for _, sid, _ in script}), seed=seed)
    tokens, last_dev, cross = {}, {}, 0
    t0 = time.perf_counter()
    for t, sid, prompt in script:
        out, m = eng.turn(sid, prompt, gen_tokens=GEN_TOKENS)
        dev = eng.rows[m.row].device
        cross += int(sid in last_dev and last_dev[sid] != dev)
        last_dev[sid] = dev
        tokens[(sid, t)] = out
    sync_rows(eng)
    wall = time.perf_counter() - t0
    placement = sorted({str(r.device) for r in eng.rows})
    print(f"{policy:8s} rows on {placement}: {len(script)} turns in "
          f"{wall:.3f} s host wall (incl. compiles); "
          f"migrations {sum(m.migrated for m in eng.metrics)}, "
          f"of which across devices {cross}")
    return tokens, cross


def four_chip_phase(model, params, devices, *, seed: int, max_seq: int,
                    n_sessions: int, n_turns: int, prompt_lens: tuple):
    import numpy as np
    script = make_script(np.random.default_rng(seed), model.cfg.vocab_size,
                         n_sessions, n_turns, prompt_lens)
    ref, _ = run_rows(model, params, devices[:1], "random", script,
                      seed=seed, max_seq=max_seq)
    for policy in ("random", "affinity"):
        got, cross = run_rows(model, params, devices, policy, script,
                              seed=seed, max_seq=max_seq)
        same = sum(got[k] == ref[k] for k in ref)
        print(f"{policy}: {same}/{len(ref)} turns token-identical to all "
              f"rows on {devices[0]}")
        check(same == len(ref), f"{policy} tokens differ from one device")
        if policy == "random":
            check(cross > 0, "no session migrated across chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="one row per chip on four chips vs all on one")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no package at {SRC}; run from a checkout of "
              f"the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devices}); this "
              f"smoke run has no CPU fallback", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro import configs
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compilation_cache
    from repro.serving.kv_cache import tree_bytes
    print(f"devices: {len(devices)} x {dev.device_kind} ({dev.platform}); "
          f"compile cache {enable_compilation_cache()}")
    compiles = CompileCounter()
    cfg = configs.get_config(ARCH)
    check(ops.get_backend() == "pallas", "kernel backend is not pallas")
    t0 = time.perf_counter()
    model, params = build(cfg, args.seed)
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads x "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; params {tree_bytes(params) / 2**30:.3f} GiB "
          f"from seed {args.seed} in {time.perf_counter() - t0:.3f} s; "
          f"kernel backend {ops.get_backend()}")

    if args.four_chips:
        four_chip_phase(model, params, devices, seed=args.seed,
                        max_seq=512, n_sessions=8, n_turns=3,
                        prompt_lens=(16, 48))
    else:
        # 16 GiB HBM: params (~4.7 GiB) + 2 rows x (8 slots x 1024) cache
        # (~0.63 GiB each) + one more cache per launch (no donation)
        slots, max_seq, rows = 8, 1024, 2
        row_cache = tree_bytes(model.cache_spec(slots, max_seq))
        print(f"plan: {rows} rows x {slots} slots x {max_seq} positions; "
              f"{row_cache / 2**30:.3f} GiB cache per row")
        eng = serve_phase(model, params, seed=args.seed, n_rows=rows,
                          slots=slots, max_seq=max_seq, n_sessions=4,
                          n_turns=3, prompt_lens=(32, 128),
                          compiles=compiles)
        row = eng.rows[0]
        kernel_check(model, row.params, row.cache, row.lengths,
                     seed=args.seed)
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
              f"of bytes_limit {stats.get('bytes_limit')}")
    n, secs = compiles.snapshot()
    print(f"backend compiles in all: {n}, {secs:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
