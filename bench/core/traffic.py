"""The one traffic generator: request sizes and arrivals from a traffic
file's parameters and a seed.

Every seed gets the same multiset of sizes and inter-arrival gaps, in
another order: sizes are the quantiles (i + 0.5) / n of the stated
distribution, then shuffled by the seed. So two seeds ask for the same
work, and a seed changes only the order and the token ids.

Distributions (``{"dist": ..., "min": .., "max": ..}``, clipped to
[min, max] and rounded):

  lognormal  ``median``, ``sigma`` (of the log)
  uniform    integers min..max
  fixed      ``value``

Open loop: ``rate_per_s`` × ``seconds`` requests with exponential gaps
(Poisson arrivals), scaled so that exactly that many fall inside the
window. Closed loop: a pool of ``pool`` sizes that the clients take in
turn, cycling.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_PHI = NormalDist()


def quantiles(spec: dict, n: int) -> List[int]:
    """The n quantiles of a length distribution, ascending."""
    kind = spec["dist"]
    lo, hi = int(spec.get("min", 1)), int(spec.get("max", 1 << 30))
    out = []
    for i in range(n):
        q = (i + 0.5) / n
        if kind == "lognormal":
            v = spec["median"] * math.exp(spec["sigma"] * _PHI.inv_cdf(q))
        elif kind == "uniform":
            v = lo + math.floor(q * (hi - lo + 1))
        elif kind == "fixed":
            v = spec["value"]
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out.append(int(min(hi, max(lo, round(v)))))
    return out


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent streams of one seed (any non-negative integer)."""
    return np.random.default_rng([int(seed), int(stream)])


class Request:
    __slots__ = ("idx", "due", "prompt", "max_new")

    def __init__(self, idx: int, due: Optional[float], prompt: List[int],
                 max_new: int):
        self.idx, self.due, self.prompt, self.max_new = idx, due, prompt, \
            max_new


def _prompts(lengths, vocab: int, rng) -> List[List[int]]:
    ids = rng.integers(1, vocab, size=int(sum(lengths)))
    out, k = [], 0
    for n in lengths:
        out.append([int(t) for t in ids[k:k + n]])
        k += n
    return out


def requests(traffic: dict, *, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The requests of one run, in the order they are sent. Open loop:
    each has its due time in seconds from the window's start. Closed
    loop: ``due`` is None and the list is the clients' shared pool."""
    if traffic["loop"] == "open":
        n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    else:
        n = int(traffic["pool"])
    p_len = quantiles(traffic["prompt"], n)
    o_len = quantiles(traffic["output"], n)
    order = rng_for(seed, 1)
    p_len = [p_len[i] for i in order.permutation(n)]
    o_len = [o_len[i] for i in order.permutation(n)]
    prompts = _prompts(p_len, vocab, rng_for(seed, 2))
    if traffic["loop"] == "open":
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        gaps = gaps[order.permutation(n)]
        t = np.cumsum(gaps) - gaps[0]
        due = (t * seconds / gaps.sum()).tolist()
    else:
        due = [None] * n
    return [Request(i, due[i], prompts[i], o_len[i]) for i in range(n)]


def check_sample(finished, *, seed: int, tokens: int) -> list:
    """Requests to compare with the reference: the longest (prompt plus
    output), then others drawn from the seed until ``tokens`` served
    tokens are in the sample. ``finished`` holds objects with
    ``.prompt`` and ``.tokens``."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i].prompt)
                  + len(finished[i].tokens))
    picked, served = [longest], len(finished[longest].tokens)
    for i in rng_for(seed, 3).permutation(len(finished)):
        if served >= tokens:
            break
        if i != longest:
            picked.append(int(i))
            served += len(finished[i].tokens)
    return [finished[i] for i in picked]
