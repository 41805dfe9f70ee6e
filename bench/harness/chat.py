"""Closed-loop chat traffic: clients that each send their next request
when the last one has finished.

Prompt and output lengths are log-normal, each given by its median, its
spread ``sigma`` (of the natural log) and a ``scale`` (a cut to fit the
run, 1 where there is none).  They are taken at ``quantiles`` fixed
quantiles, the midpoints of equal bins, and paired by a fixed stride, so
that every seed serves the same set of lengths.  Client ``c`` starts at
pair ``c * quantiles // clients`` and walks the table in order, wrapping
around.  The seed chooses the order in which the clients first send and
the token ids of every prompt.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, Iterator, List, Tuple

import numpy as np


def quantile_lengths(dist: Dict, q: int) -> List[int]:
    """``q`` lengths of a log-normal at the midpoints of q equal bins."""
    med = dist["median"] * dist.get("scale", 1.0)
    return [max(1, int(round(med * math.exp(
        dist["sigma"] * NormalDist().inv_cdf((i + 0.5) / q)))))
        for i in range(q)]


def length_table(mix: Dict) -> List[Tuple[int, int]]:
    """The (prompt, output) length pairs of the mix."""
    q = mix["quantiles"]
    prompts = quantile_lengths(mix["prompt"], q)
    outputs = quantile_lengths(mix["output"], q)
    stride = mix["pair_stride"]
    if math.gcd(stride, q) != 1:
        raise ValueError("pair_stride must be coprime with quantiles")
    return [(prompts[i], outputs[(i * stride) % q]) for i in range(q)]


class Client:
    """One client's endless stream of requests: (prompt ids, output len)."""

    def __init__(self, index: int, mix: Dict, vocab: int, seed: int):
        self.index = index
        self.table = length_table(mix)
        self.start = index * len(self.table) // mix["clients"]
        self.rng = np.random.default_rng([seed, index])
        self.vocab = vocab
        self.sent = 0

    def next(self) -> Tuple[np.ndarray, int]:
        p, o = self.table[(self.start + self.sent) % len(self.table)]
        self.sent += 1
        return self.rng.integers(1, self.vocab, p, dtype=np.int32), o


def clients(mix: Dict, vocab: int, seed: int) -> Iterator[Client]:
    """The mix's clients in the order the seed has them send first."""
    order = np.random.default_rng([seed, 1 << 20]).permutation(mix["clients"])
    return [Client(int(i), mix, vocab, seed) for i in order]
