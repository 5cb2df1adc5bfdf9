"""Operations and bytes the served turns require, from shapes alone.

Counted from what each turn asks of the model, whatever the
implementation does: a later PR that skips work or batches it cannot
raise its own share of a peak by changing these counts.

A request (a turn, on a session that already holds ``hist`` positions)
feeds ``fed`` positions (its prompt, then its decode inputs) and produces
``gen`` tokens.  Position ``hist + j`` attends to ``hist + j + 1``
positions.  Of the fed positions the first ``prefilled`` (0 where the
request has none) go through one parallel prefill pass, the flash
attention's work; the rest go through the decode attention.
"""
from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    return d * h * dh + 2 * d * k * dh + h * dh * d + 3 * d * f


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position over every layer, bf16."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)


def _context_sum(hist: int, fed: int) -> int:
    """Sum over the fed positions of the context each attends to."""
    return fed * hist + fed * (fed + 1) // 2


def attention_flops(cfg: dict, hist: int, fed: int) -> float:
    """QK^T and PV: 4 * heads * head_dim per attended position, per layer."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * _context_sum(hist, fed))


def attention_bytes(cfg: dict, hist: int, prompt: int, dec: int) -> float:
    """The history and the prompt (if any) are read once; each of the
    ``dec`` decode inputs after the prompt reads its whole context again
    (decoding is sequential)."""
    first = hist + prompt
    ctx = dec * first + dec * (dec + 1) // 2
    return float(kv_bytes_per_position(cfg)
                 * ((first if prompt else 0) + ctx))


def prefill_bytes(cfg: dict, n: int) -> float:
    """A prefill pass over ``n`` positions with no history reads each
    position's queries, keys and values once and writes its output, bf16."""
    per = (2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) \
        * cfg["head_dim"] * 2
    return float(cfg["num_hidden_layers"] * per * n)


def model_flops(cfg: dict, hist: int, fed: int, gen: int) -> float:
    """2 x matmul parameters per fed position, the LM head for each
    generated token, and attention over each position's real context."""
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * gen
    return (2.0 * layer_matmul_params(cfg) * cfg["num_hidden_layers"] * fed
            + head + attention_flops(cfg, hist, fed))


def _least(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def least_decode_attention_seconds(cfg: dict, reqs, peaks: dict) -> float:
    """The least time of the attention that goes through the decode
    attention, summed over ``reqs`` (dicts with ``hist``, ``prompt`` (the
    tokens), ``fed`` and optionally ``prefilled``): the larger of its FLOPs
    over peak and its K/V bytes over HBM bandwidth."""
    flops = nbytes = 0.0
    for r in reqs:
        pre = r.get("prefilled", 0)
        hist, fed = r["hist"] + pre, r["fed"] - pre
        prompt = max(len(r["prompt"]) - pre, 0)
        flops += attention_flops(cfg, hist, fed)
        nbytes += attention_bytes(cfg, hist, prompt, fed - prompt)
    return _least(flops, nbytes, peaks)


def least_prefill_attention_seconds(cfg: dict, reqs, peaks: dict) -> float:
    """The same for the prefill pass of each request that has one (no
    history: causal attention over its ``prefilled`` positions)."""
    pre = [r["prefilled"] for r in reqs if r.get("prefilled")]
    flops = sum(attention_flops(cfg, 0, n) for n in pre)
    nbytes = sum(prefill_bytes(cfg, n) for n in pre)
    return _least(flops, nbytes, peaks)
