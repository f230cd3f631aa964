"""The one traffic generator: every traffic mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) gives length distributions, an
arrival process and the serving pool. Requests come in blocks of
``block``; within a block the prompt lengths, output lengths and
inter-arrival gaps are the block's quantiles of their distributions, put
in an order drawn from the mix's own ``schedule_seed``. So every run of a
mix sends the same sizes at the same times, and the run's seed draws the
tokens (and the weights): under a queue near its knee, another order of
the same sizes moves a tail by tens of percent, which would swamp any
change a later PR makes.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Req:
    due: float           # seconds after the traffic starts (open loop)
    prompt: np.ndarray   # (Lp,) int32
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent streams of one seed (any non-negative whole number)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of a length or gap distribution.

    ``lognormal``: a log-normal of the given ``mean`` and ``sigma`` (of
    the log), truncated to [``lo``, ``hi``] as a source's length filter
    drops what lies outside, rounded to whole tokens;
    ``exponential``: -mean * log(1 - u).
    """
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        lo, hi, sigma = float(dist["lo"]), float(dist["hi"]), \
            float(dist["sigma"])
        nd = statistics.NormalDist(math.log(float(dist["mean"]))
                                   - sigma * sigma / 2, sigma)
        a, b = nd.cdf(math.log(lo)), nd.cdf(math.log(hi))
        x = np.exp([nd.inv_cdf(a + (b - a) * v) for v in u])
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    if kind == "exponential":
        return -float(dist["mean"]) * np.log1p(-u)
    raise ValueError(f"unknown distribution {kind!r}")


def lengths(traffic: dict) -> tuple[np.ndarray, np.ndarray]:
    """Every prompt and output length the mix can send (sorted)."""
    n = int(traffic["block"])
    return (np.unique(quantiles(traffic["prompt"], n)),
            np.unique(quantiles(traffic["output"], n)))


class Stream:
    """The seed's request sequence, made block by block on demand."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.t = traffic
        self.vocab = vocab
        self.block = int(traffic["block"])
        rate = traffic.get("rate_per_s")
        self.gaps = (quantiles({"dist": "exponential", "mean": 1.0 / rate},
                               self.block) if rate else None)
        self.prompts = quantiles(traffic["prompt"], self.block)
        self.outputs = quantiles(traffic["output"], self.block)
        self.order = rng_for(int(traffic["schedule_seed"]), 0)
        self.tokens = rng_for(seed, 1)
        self.reqs: list[Req] = []
        self.clock = 0.0

    def _more(self):
        p = self.order.permutation(self.prompts)
        o = self.order.permutation(self.outputs)
        g = (self.order.permutation(self.gaps) if self.gaps is not None
             else np.zeros(self.block))
        for i in range(self.block):
            self.clock += float(g[i])
            toks = self.tokens.integers(3, self.vocab, size=int(p[i]),
                                        dtype=np.int32)
            self.reqs.append(Req(self.clock, toks, int(o[i])))

    def __getitem__(self, i: int) -> Req:
        while i >= len(self.reqs):
            self._more()
        return self.reqs[i]
