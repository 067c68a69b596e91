"""One general traffic generator. A traffic mix is a data file under
tpubench/traffic/; nothing here knows a cell's name.

Serving traffic is stratified: prompt lengths and answer lengths are fixed
multisets (the quantile mid-points of the distributions in the file), in
blocks of `block` requests. The seed permutes them within each block and draws
the token ids, so every seed and every block offers the same tokens and the
same prefill work, in another order.

Training traffic is a seeded first-order Markov chain over the vocabulary
(labels are the inputs shifted by one), so that a falling loss has a reason.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _midpoints(n):
    return [(i + 0.5) / n for i in range(n)]


def quantile_midpoints(dist, n):
    """The n quantile mid-points of a distribution given as a dict:
    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b},
    {"dist": "uniform", "min": a, "max": b} or {"dist": "fixed", "value": v}."""
    kind = dist["dist"]
    ps = _midpoints(n)
    if kind == "fixed":
        return [float(dist["value"])] * n
    if kind == "uniform":
        return [dist["min"] + p * (dist["max"] - dist["min"]) for p in ps]
    if kind == "lognormal":
        mu = math.log(dist["median"])
        return [min(max(math.exp(mu + dist["sigma"] * _NORMAL.inv_cdf(p)),
                        dist["min"]), dist["max"]) for p in ps]
    raise ValueError(f"unknown distribution {kind!r}")


def to_buckets(values, buckets):
    """Each value moved to the nearest bucket (ties to the smaller)."""
    buckets = sorted(buckets)
    return [min(buckets, key=lambda b: (abs(b - v), b)) for v in values]


def block_multisets(mix):
    """(prompt lengths, answer lengths) of one block, each sorted: the same
    for every seed."""
    n = int(mix["block"])
    prompts = quantile_midpoints(mix["prompt"], n)
    if "buckets" in mix["prompt"]:
        prompts = to_buckets(prompts, mix["prompt"]["buckets"])
    prompts = sorted(int(round(p)) for p in prompts)
    answers = sorted(int(round(a))
                     for a in quantile_midpoints(mix["answer"], n))
    return prompts, answers


def backlog(mix, seed, count, vocab_size, max_total):
    """`count` requests for an offline batch, all present from the start:
    the block multisets repeated and permuted, no due times. Prompt plus
    answer may not pass `max_total` tokens, the model's positions."""
    rng = np.random.default_rng([int(seed), 0x0FF1])
    prompts, answers = block_multisets(mix)
    if max(prompts) + max(answers) > max_total:
        raise ValueError(
            f"prompt {max(prompts)} + answer {max(answers)} > {max_total}: "
            "choose traffic on which no request is cut")
    out = []
    while len(out) < count:
        pl = rng.permutation(prompts)
        al = rng.permutation(answers)
        for plen, alen in zip(pl, al):
            out.append({"index": len(out), "block": len(out) // len(prompts),
                        "prompt": rng.integers(1, vocab_size,
                                               int(plen)).tolist(),
                        "answer_len": int(alen)})
    return out[:count]


class MarkovTokens:
    """Token batches from a first-order Markov chain: every token has
    `successors` possible next tokens, drawn with a skew toward small ids
    (vocab * u**skew), so both the unigram and the bigram statistics are
    learnable. The chain is made from the seed; batches follow from it."""

    def __init__(self, seed, vocab_size, successors=4, skew=6.0):
        self._rng = np.random.default_rng([int(seed), 0x7A1])
        self._vocab = int(vocab_size)
        self._skew = float(skew)
        self._succ = self._draw((self._vocab, int(successors)))

    def _draw(self, shape):
        u = self._rng.random(shape)
        return np.minimum((self._vocab * u ** self._skew).astype(np.int32),
                          self._vocab - 1)

    def batch(self, rows, seq):
        ids = np.empty((rows, seq), np.int32)
        ids[:, 0] = self._draw((rows,))
        pick = self._rng.integers(0, self._succ.shape[1], (rows, seq))
        for t in range(1, seq):
            ids[:, t] = self._succ[ids[:, t - 1], pick[:, t]]
        return ids
