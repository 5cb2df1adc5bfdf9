"""Operations and bytes the served turns require, from shapes alone.

Counted from what each turn asks of the model, whatever the
implementation does: a later PR that skips work or batches it cannot
raise its own share of a peak by changing these counts.

What a position costs is the architecture's (``families/<model_type>.py``);
this module sums it over requests.  A request (a turn, on a session that
already holds ``hist`` positions) feeds ``fed`` positions (its prompt, then
its decode inputs) and produces ``gen`` tokens.  Position ``hist + j``
attends to ``hist + j + 1`` positions.  Of the fed positions the first
``prefilled`` (0 where the request has none) go through one parallel
prefill pass, the flash attention's work; the rest go through the decode
attention.
"""
from __future__ import annotations

from . import spec


def _context_sum(hist: int, fed: int) -> int:
    """Sum over the fed positions of the context each attends to."""
    return fed * hist + fed * (fed + 1) // 2


def attention_flops(cfg: dict, path: str, hist: int, fed: int) -> float:
    """Attention of ``fed`` positions after ``hist`` on ``path``
    (``"prefill"`` or ``"decode"``): the family's FLOPs per attended
    position, over every layer, times the positions attended."""
    return (spec.family(cfg).attention_flops(cfg, path)
            * _context_sum(hist, fed))


def attention_bytes(cfg: dict, hist: int, prompt: int, dec: int) -> float:
    """The history and the prompt (if any) are read once; each of the
    ``dec`` decode inputs after the prompt reads its whole context again
    (decoding is sequential)."""
    first = hist + prompt
    ctx = dec * first + dec * (dec + 1) // 2
    return float(spec.family(cfg).cache_bytes(cfg)
                 * ((first if prompt else 0) + ctx))


def model_flops(cfg: dict, hist: int, fed: int, gen: int,
                prefilled: int) -> float:
    """The layers' matmuls for each fed position, the LM head for each
    generated token, and attention over each position's real context, the
    first ``prefilled`` fed positions on the prefill path and the rest on
    the decode path."""
    fam = spec.family(cfg)
    return (fam.matmul_flops(cfg) * fed + fam.head_flops(cfg) * gen
            + attention_flops(cfg, "prefill", hist, prefilled)
            + attention_flops(cfg, "decode", hist + prefilled,
                              fed - prefilled))


def _least(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def least_decode_attention_seconds(cfg: dict, reqs, peaks: dict) -> float:
    """The least time of the attention that goes through the decode
    attention, summed over ``reqs`` (dicts with ``hist``, ``prompt`` (the
    tokens), ``fed`` and optionally ``prefilled``): the larger of its FLOPs
    over peak and its cache bytes over HBM bandwidth."""
    flops = nbytes = 0.0
    for r in reqs:
        pre = r.get("prefilled", 0)
        hist, fed = r["hist"] + pre, r["fed"] - pre
        prompt = max(len(r["prompt"]) - pre, 0)
        flops += attention_flops(cfg, "decode", hist, fed)
        nbytes += attention_bytes(cfg, hist, prompt, fed - prompt)
    return _least(flops, nbytes, peaks)


def least_prefill_attention_seconds(cfg: dict, reqs, peaks: dict) -> float:
    """The same for the prefill pass of each request that has one (no
    history: causal attention over its ``prefilled`` positions, each
    position's queries, keys, values and output read or written once)."""
    pre = [r["prefilled"] for r in reqs if r.get("prefilled")]
    per = spec.family(cfg).prefill_bytes(cfg)
    flops = sum(attention_flops(cfg, "prefill", 0, n) for n in pre)
    nbytes = sum(float(per * n) for n in pre)
    return _least(flops, nbytes, peaks)
