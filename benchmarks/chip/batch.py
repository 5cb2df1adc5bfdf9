"""Traffic kind ``batch``: offline batches of answers through the
program's own server steps.

A mix gives ``batch`` requests a batch, each a prompt of ``prompt`` tokens
answered greedily with ``answer`` tokens.  A batch runs, in order:

1. the program's prefill step (``repro.launch.steps.make_prefill_step``:
   one forward over the prompts, with the Pallas flash attention);
2. one jitted call of this module that picks the first token from the
   prefill's logits and widens the cache from ``prompt`` to
   ``prompt + answer`` positions (the program has no call for this);
3. ``answer - 1`` calls of the program's serve step (``make_serve_step``:
   ``Model.decode_step`` with the Pallas decode attention, then the greedy
   pick), each fed the tokens the last one produced, dispatched without
   waiting; then one call that stacks the tokens.

The window runs batches back to back, ``ahead`` of them dispatched before
it waits for the oldest one's tokens; once ``seconds`` have passed it
sends nothing more, and the clock is read after the last batch's tokens
are back, so every batch sent counts over all of that time.  The check compares a sample of the window's
requests, drawn from the seed, with the longest in it.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, List

import numpy as np

from . import spec, traffic

SPAN = "bench.window"    # host annotation around the service


@dataclasses.dataclass
class Program:
    params: Any
    prefill: Any
    widen: Any
    serve: Any
    stack: Any
    lengths: List[Any]   # the serve step's ``lengths``, one array a step
    device: Any


def _jit(mesh, bundle):
    import jax
    from repro.launch import steps
    return jax.jit(
        bundle.fn,
        in_shardings=steps.to_shardings(mesh, bundle.in_shardings),
        out_shardings=steps.to_shardings(mesh, bundle.out_shardings),
        donate_argnums=bundle.donate_argnums)


def build(cfg: dict, mix: dict, seed: int, devices) -> Program:
    """Weights from ``seed`` in one jitted call and the batch's programs,
    each compiled and run once on the cell's shapes."""
    import jax
    import jax.numpy as jnp
    from repro.configs.shapes import ShapeConfig
    from repro.launch import steps
    from repro.launch.mesh import make_local_mesh
    from . import weights
    B, P, G = mix["batch"], mix["prompt"], mix["answer"]
    mc = spec.model_config(cfg)
    mesh = make_local_mesh()
    prefill = _jit(mesh, steps.make_prefill_step(
        mc, ShapeConfig("prefill", P, B, "prefill"), mesh))
    serve = _jit(mesh, steps.make_serve_step(
        mc, ShapeConfig("decode", P + G, B, "decode"), mesh))

    def widen_(logits, cache):
        def pad(a):         # (layers, batch, positions, ...)
            return jnp.pad(a, [(0, 0), (0, 0), (0, G)]
                           + [(0, 0)] * (a.ndim - 3))
        first = jnp.argmax(logits, -1).astype(jnp.int32)
        return first, jax.tree_util.tree_map(pad, cache)

    lengths = [jax.device_put(np.full((B,), P + i, np.int32), devices[0])
               for i in range(G - 1)]
    prog = Program(params=weights.make(cfg, seed, devices[0]),
                   prefill=prefill, widen=jax.jit(widen_), serve=serve,
                   stack=jax.jit(lambda toks: jnp.stack(toks, 1)),
                   lengths=lengths, device=devices[0])
    zeros = jax.device_put(np.zeros((B, P), np.int32), devices[0])
    toks = dispatch(prog, zeros, steps=1)
    jax.block_until_ready((prog.params, toks))
    return prog


def dispatch(prog: Program, prompts, steps: int = -1):
    """One batch, dispatched without waiting: the device array of its
    tokens, (batch, answer).  ``steps`` serve calls (all by default); the
    set-up's warm run makes one and stacks it as often as a batch does."""
    logits, cache = prog.prefill(prog.params, {"tokens": prompts})
    tok, cache = prog.widen(logits, cache)
    toks = [tok]
    todo = prog.lengths if steps < 0 else prog.lengths[:steps]
    for lengths in todo:
        tok, cache = prog.serve(prog.params, cache, tok, lengths)
        toks.append(tok)
    toks += [tok] * (len(prog.lengths) + 1 - len(toks))
    return prog.stack(toks)


def serve_window(prog: Program, mix: dict, seed: int, seconds: float,
                 vocab: int, annotate):
    """Batches back to back, ``mix["ahead"]`` of them dispatched before
    the run waits for the oldest, so that the chip stays fed while the
    host stands still; nothing is sent once ``seconds`` have passed (the
    first batch always is)."""
    import jax
    records, flight, k = [], collections.deque(), 0
    t0 = time.perf_counter()
    with annotate(SPAN):
        while True:
            while len(flight) < mix["ahead"] and (
                    k == 0 or time.perf_counter() - t0 < seconds):
                prompts = traffic.batch_prompts(mix, seed, k, vocab)
                with annotate("bench.dispatch", batch=k):
                    start = time.perf_counter() - t0
                    out = dispatch(prog, jax.device_put(prompts, prog.device))
                flight.append((k, prompts, out, start))
                k += 1
            if not flight:
                break
            b, prompts, out, start = flight.popleft()
            with annotate("bench.wait", batch=b):
                out = np.asarray(out)
            done = time.perf_counter() - t0
            for row in range(prompts.shape[0]):
                records.append(dict(
                    batch=b, prompt=prompts[row].tolist(),
                    out=out[row].tolist(), gen=mix["answer"], hist=0,
                    fed=mix["prompt"] + mix["answer"] - 1,
                    prefilled=mix["prompt"], start=start, done=done))
    return records, dict(window_s=done, in_service_s=done)


def sample(records: List[dict], n: int, seed: int) -> List[dict]:
    """``n`` finished requests drawn from the seed, the longest first."""
    rng = np.random.default_rng([seed, 2])
    done = [r for r in records if r["done"] is not None]
    order = sorted(range(len(done)), key=lambda i: -len(done[i]["out"]))
    rest = rng.permutation(order[1:])[:n - 1] if n > 1 else []
    return [done[order[0]]] + [done[i] for i in sorted(rest)]


def sequences(recs: List[dict], vocab: int):
    """Each request's prompt then answer, the targets (answer token ``i``
    at the position before it, -1 elsewhere) and the count of malformed
    answers (of the wrong length, or with an id outside the vocabulary;
    not compared)."""
    width = max(len(r["prompt"]) + r["gen"] for r in recs)
    tokens = np.zeros((len(recs), width), np.int32)
    targets = np.full((len(recs), width), -1, np.int32)
    malformed = 0
    for b, r in enumerate(recs):
        p, out = len(r["prompt"]), r["out"]
        seq = (r["prompt"] + out)[:width]
        tokens[b, :len(seq)] = seq
        if len(out) == r["gen"] and all(0 <= x < vocab for x in out):
            targets[b, p - 1:p - 1 + len(out)] = out
        else:
            malformed += 1
    return tokens, targets, malformed


# -- the kind's interface to ``harness.run_cell`` ------------------------------

def prepare(cfg: dict, mix: dict, seed: int, devices):
    """(program state, what the check keeps once the program is freed)."""
    return build(cfg, mix, seed, devices), None


def serve(prog: Program, cfg: dict, mix: dict, seed: int, seconds: float,
          annotate):
    records, facts = serve_window(prog, mix, seed, seconds,
                                  cfg["vocab_size"], annotate)
    dones = sorted({r["batch"]: r["done"] for r in records}.values())
    gaps = np.diff([0.0] + dones)
    facts["describe"] = (
        f"{len(dones)} batches of {mix['batch']} x ({mix['prompt']} prompt "
        f"+ {mix['answer']} answer) tokens, {mix['ahead']} ahead; seconds "
        f"between batches done: min {gaps.min():.4f}, median "
        f"{np.median(gaps):.4f}, max {gaps.max():.4f}")
    return records, facts


def compared(records: List[dict], _kept, cfg: dict, mix: dict, seed: int):
    return sequences(sample(records, mix["compare_requests"], seed),
                     cfg["vocab_size"])


def end_to_end(records: List[dict], facts: dict, seconds: float,
               mix: dict) -> dict:
    """Answer tokens completed per second, over all the window's time."""
    return {"tokens_per_s": sum(len(r["out"]) for r in records)
            / facts["window_s"]}
