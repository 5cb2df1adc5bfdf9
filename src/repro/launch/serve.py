"""Serving launcher: affinity-routed multi-row engine.

``python -m repro.launch.serve --arch granite-3-2b --policy affinity``
serves the published config of ``--arch`` with random weights from a seed
(one row per local device, round-robin), drives synthetic multi-turn
sessions through the continuous-batching engine, and prints the TTFT /
migration summary (paper §7.2 applied).  ``--smoke`` serves the reduced
same-family config instead, which is what runs on a CPU.  The summary's
``ttft_*`` values are the engine's virtual clock, not device times.
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.launch.compile_cache import enable_compilation_cache
from repro.models import build_model
from repro.serving import ServingEngine, make_adapter


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--policy", default="affinity",
                    choices=["affinity", "adapter_affinity", "random",
                             "least_loaded"])
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--turns", type=int, default=4)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()

    enable_compilation_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, n_rows=args.rows,
                        max_slots=args.slots, max_seq=args.max_seq,
                        policy=args.policy)
    eng.adapters.register(
        make_adapter(jax.random.PRNGKey(1), "support-bot", cfg.d_model,
                     cfg.vocab_size))
    for i in range(args.sessions):
        eng.open_session(f"s{i}",
                         adapter="support-bot" if i % 3 == 0 else None)
    t = 0.0
    for turn in range(args.turns):
        for i in range(args.sessions):
            prompt = [1 + (i + turn) % 17, 2, 3]
            _, m = eng.turn(f"s{i}", prompt, gen_tokens=args.gen, now=t)
            t += 0.002
    print(f"policy={args.policy} model={cfg.name} devices={jax.devices()}")
    for k, v in eng.summary().items():
        print(f"  {k:22s} {v}")


if __name__ == "__main__":
    main()
