"""One general generator for every traffic mix in bench/traffic/*.json.

A mix is data: the loop kind, the concurrency, and the prompt and output
length distributions. The sizes are fixed by the mix alone, so every seed
serves the same work:

  - each distribution is cut into `pool` strata (stratified quantiles of
    the clipped lognormal);
  - request j of the stream takes prompt stratum ⌊frac((j + ½)·φ)·pool⌋
    and output stratum ⌊frac((j + ½)·ρ)·pool⌋, with φ = (√5 − 1)/2 and
    ρ = √2 − 1: a Kronecker sequence, so any run of consecutive requests
    spreads evenly over both distributions and over their pairings.

The seed decides the token ids, and the order in which the first wave
takes the slots.

The first wave of a closed loop starts near steady state, and is the same
set for every seed. Worker w of W takes the request at quantile (w + ½)/W
of the length-biased distribution of lives (prefill chunks plus output
tokens) over the first `pool` stream pairs, and the residual
1 + ⌊frac((w + ½)·φ)·life⌋ of that life:
  - a residual inside the output phase gives a request whose prompt is
    prefilled during set-up and which then emits only the residual tokens;
  - a residual inside the prefill phase gives a request whose prompt is the
    residual's prefill span, submitted when the window opens.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

PHI = (math.sqrt(5.0) - 1.0) / 2.0
RHO = math.sqrt(2.0) - 1.0


def lengths(dist: dict, n: int) -> np.ndarray:
    """n stratified quantiles of a lognormal given by its median and sigma,
    rounded and clipped to [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist()
    mu = math.log(dist["median"])
    out = np.array([math.exp(mu + dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
                    for i in range(n)])
    return np.clip(np.rint(out), dist["min"], dist["max"]).astype(np.int64)


def stratum(j: int, step: float, n: int) -> int:
    return int(math.fmod((j + 0.5) * step, 1.0) * n)


@dataclasses.dataclass
class Item:
    """One request to submit: its prompt, its output length, and whether
    its prompt is prefilled during set-up (first wave, output phase)."""
    key: tuple
    prompt: list
    max_new: int
    in_setup: bool = False


class Plan:
    """The seeded request stream of one run of one traffic mix."""

    def __init__(self, mix: dict, seed: int, vocab: int, chunk: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.chunk = int(chunk)
        self.n = int(mix["pool"])
        self.prompt_strata = lengths(mix["prompt"], self.n)
        self.output_strata = lengths(mix["output"], self.n)
        self._next = 0

    def sizes(self, j: int) -> tuple[int, int]:
        """(prompt length, output length) of request j of the stream."""
        return (int(self.prompt_strata[stratum(j, PHI, self.n)]),
                int(self.output_strata[stratum(j, RHO, self.n)]))

    def _tokens(self, tag: int, i: int, n: int) -> list:
        rng = np.random.default_rng([self.seed, tag, i])
        return rng.integers(0, self.vocab, size=int(n)).tolist()

    def next_request(self) -> Item:
        j = self._next
        self._next += 1
        plen, olen = self.sizes(j)
        return Item(("stream", j), self._tokens(1, j, plen), olen)

    def life(self, plen: int, olen: int) -> int:
        return -(-plen // self.chunk) + olen

    def first_wave(self, workers: int) -> list[Item]:
        pairs = [self.sizes(j) for j in range(self.n)]
        lives = np.array([self.life(*p) for p in pairs], np.float64)
        cdf = np.cumsum(lives) / lives.sum()
        wave = []
        for w in range(workers):
            i = int(np.searchsorted(cdf, (w + 0.5) / workers))
            plen, olen = pairs[i]
            life = int(lives[i])
            r = 1 + int(math.fmod((w + 0.5) * PHI, 1.0) * life)
            if r <= olen:
                wave.append(Item(("first", w), self._tokens(3, w, plen), r,
                                 in_setup=True))
            else:
                span = min(plen, (r - olen) * self.chunk)
                wave.append(Item(("first", w), self._tokens(3, w, span),
                                 olen))
        order = np.random.default_rng([self.seed, 2]).permutation(workers)
        return [wave[int(k)] for k in order]
