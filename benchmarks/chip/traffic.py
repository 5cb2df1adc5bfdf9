"""The generators of traffic, one for each kind of mix.

``kind: "batch"`` (``batch_prompts``): batch ``k`` of a run is ``batch``
prompts of ``prompt`` token ids, drawn from ``--seed`` and ``k``; every
seed sends the same shapes in the same order.

``kind: "sessions"`` (``schedule``): open-loop multi-turn sessions.  A mix
file (``traffic/<mix>.json``) gives the rate in turns per second,
the number of sessions and rows, the routing policy, the length
distributions and ``order_seed``.  For a window of ``seconds`` the
generator makes ``round(rate * seconds)`` turns: inter-arrival gaps at the
mid-quantiles of the exponential, session popularity ranks by Zipf counts
(largest remainder), prompt and answer lengths at the mid-quantiles of
their distributions, in an order drawn from ``order_seed``.  So the
sequence of arrivals and sizes is the mix's own, a replayed schedule: two
runs of a cell do the same work in the same order.  The run's ``--seed``
draws the token ids and which session holds which popularity rank (and,
elsewhere, the weights).

A session's first turn takes its prompt length from ``first_prompt``, its
later turns from ``prompt``.  No session passes ``max_seq`` positions: a
turn that would not fit goes to the opened session with the most room,
else to the first session not yet opened.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Turn:
    due: float          # seconds after the window opens
    sid: str
    prompt: List[int]
    gen: int


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of ``dist``, clipped to [lo, hi]."""
    if n == 0:
        return np.zeros((0,), np.int64)
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["lo"], dist["hi"]
    if dist["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo)
    elif dist["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _zipf_counts(n_turns: int, n_sessions: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n_sessions + 1) ** s
    exact = n_turns * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:n_turns - counts.sum()]] += 1
    return counts


def batch_prompts(mix: dict, seed: int, k: int, vocab: int) -> np.ndarray:
    """Batch ``k``'s prompts, (batch, prompt) int32 ids in [1, vocab)."""
    rng = np.random.default_rng([seed, 3, k])
    return rng.integers(1, vocab, (mix["batch"], mix["prompt"]),
                        dtype=np.int32)


def n_turns(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_turns_per_s"] * seconds)))


def session_ids(mix: dict) -> List[str]:
    return [f"s{i}" for i in range(mix["sessions"])]


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             max_seq: int) -> List[Turn]:
    if mix["kind"] != "sessions":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    order = np.random.default_rng(mix["order_seed"])
    rng = np.random.default_rng(seed)
    n = n_turns(mix, seconds)
    rate = mix["rate_turns_per_s"]
    n_sess = mix["sessions"]

    gaps = order.permutation(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
    due = (np.cumsum(gaps) - 0.5 * gaps) * (seconds / gaps.sum())
    ranks = order.permutation(np.repeat(
        np.arange(n_sess), _zipf_counts(n, n_sess, mix["zipf_s"])))
    firsts = list(order.permutation(_quantiles(mix["first_prompt"], n_sess)))
    laters = list(order.permutation(_quantiles(mix["prompt"], n)))
    answers = order.permutation(_quantiles(mix["answer"], n))

    used = [0] * n_sess
    picked = []                        # (rank, prompt length)
    for k in range(n):
        gen = int(answers[k])
        r = int(ranks[k])
        opened = sorted((i for i in range(n_sess) if used[i] and i != r),
                        key=lambda i: (used[i], i))
        fresh = [i for i in range(n_sess) if not used[i] and i != r]
        for c in [r] + opened + fresh:
            plen = int(firsts[-1] if not used[c] else laters[-1])
            if used[c] + plen + gen <= max_seq - 1:
                (firsts if not used[c] else laters).pop()
                picked.append((c, plen))
                used[c] += plen + gen
                break
        else:
            raise ValueError(f"mix {mix['name']!r} does not fit "
                             f"{n_sess} x {max_seq} positions")

    hot = rng.permutation(n_sess)      # which session holds which rank
    sids = session_ids(mix)
    return [Turn(due=float(due[k]), sid=sids[hot[c]],
                 prompt=rng.integers(1, vocab, plen).tolist(),
                 gen=int(answers[k]))
            for k, (c, plen) in enumerate(picked)]
