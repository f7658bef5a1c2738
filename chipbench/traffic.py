"""One generator for every traffic mix: the mix is a data file.

Lengths are log-normal and clipped.  So that the seed changes the
order of the work and not its amount, every seed draws from the same
set of sizes (quantiles of the distribution) and of arrival gaps
(quantiles of the exponential), each permuted by the seed; token ids
are uniform over the vocabulary.

* ``closed`` loop: the queue is topped up so that it never holds fewer
  than ``capacity`` requests; the stream is pass after pass over the
  size set, each pass in a fresh seeded order.
* ``open`` loop: Poisson arrivals at ``rate_per_s``; the requests due
  in a window of ``seconds`` are fixed in number.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray      # int32 token ids
    max_tokens: int
    arrival_s: float = 0.0  # scheduled, from the window's opening (open loop)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per use; any non-negative seed (> 32 bits)."""
    return np.random.default_rng([stream, seed])


def length_set(spec: dict, n: int) -> np.ndarray:
    """``n`` clipped log-normal lengths at the quantiles (i + 1/2) / n."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lens = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(lens, spec["min"], spec["max"]).astype(np.int64)


def gap_set(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps (mean 1/rate) at the quantiles (i + 1/2) / n."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _prompt(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)


class ClosedStream:
    """Endless closed-loop request stream: pass after pass over the set."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        n = mix["set_size"]
        self.prompts = length_set(mix["prompt_tokens"], n)
        self.outputs = length_set(mix["output_tokens"], n)
        self.rng = rng_for(seed, 1)
        self._pass: list[Request] = []

    def _refill(self) -> None:
        pi = self.rng.permutation(len(self.prompts))
        oi = self.rng.permutation(len(self.outputs))
        self._pass = [Request(_prompt(self.rng, int(self.prompts[a]),
                                      self.vocab), int(self.outputs[b]))
                      for a, b in zip(pi, oi)]
        self._pass.reverse()

    def next(self) -> Request:
        if not self._pass:
            self._refill()
        return self._pass.pop()


def open_schedule(mix: dict, seed: int, seconds: float, vocab: int
                  ) -> list[Request]:
    """Requests with scheduled arrivals covering ``seconds``."""
    rate = mix["rate_per_s"]
    n = max(1, math.ceil(rate * seconds))
    rng = rng_for(seed, 2)
    gaps = rng.permutation(gap_set(rate, n))
    prompts = rng.permutation(length_set(mix["prompt_tokens"], n))
    outputs = rng.permutation(length_set(mix["output_tokens"], n))
    t = np.cumsum(gaps)
    return [Request(_prompt(rng, int(p), vocab), int(o), float(a))
            for a, p, o in zip(t, prompts, outputs)]
