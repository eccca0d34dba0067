"""The one traffic generator: a traffic mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) names the generator and its
parameters.  ``closed_loop`` is a fixed number of clients with no think
time: each client sends its next request when its last one has delivered
its final token.  Lengths come from lognormal fits (mean and median) to a
published trace, clipped, and drawn as a stratified sample: each block of
``stratum`` requests holds the quantiles of the fit at evenly spaced
probabilities, shuffled by the seed.  Every seed therefore offers the same
length mix, in another order; within each block exactly
``deterministic_share`` of the requests ask for determinism.  Prompt
token ids are uniform over the vocabulary.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int  # order of sending
    prompt: List[int]
    max_new_tokens: int
    deterministic: bool


def lognormal_params(mean: float, median: float):
    """(mu, sigma) of the lognormal with this mean and median."""
    mu = math.log(median)
    return mu, math.sqrt(2.0 * (math.log(mean) - mu))


def quantile_lengths(fit: Dict, n: int) -> np.ndarray:
    """The fit's quantiles at probabilities (i + 1/2) / n, clipped."""
    mu, sigma = lognormal_params(fit["mean"], fit["median"])
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lens = np.rint(np.exp(mu + sigma * z))
    return np.clip(lens, fit["min"], fit["max"]).astype(np.int64)


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    # seeds are any whole number (the driver's exceed 32 bits)
    return np.random.default_rng([int(seed) % (1 << 63), stream])


class ClosedLoop:
    """Requests of a closed-loop mix, in sending order, drawn from a seed."""

    def __init__(self, mix: Dict, seed: int, vocab_size: int):
        if mix["generator"] != "closed_loop":
            raise ValueError(f"unknown traffic generator {mix['generator']!r}")
        self.mix = mix
        self.clients = int(mix["clients"])
        self.vocab = int(vocab_size)
        self.seed = int(seed)
        k = int(mix["stratum"])
        self.stratum = k
        self.prompt_lens = quantile_lengths(mix["prompt"], k)
        self.output_lens = quantile_lengths(mix["output"], k)
        share = float(mix["deterministic_share"])
        self.n_det = int(round(share * k))
        if abs(self.n_det - share * k) > 1e-9:
            raise ValueError("deterministic_share x stratum must be whole")

    def __iter__(self) -> Iterator[RequestSpec]:
        order = seeded_rng(self.seed, 0)
        tokens = seeded_rng(self.seed, 1)
        k, i = self.stratum, 0
        while True:
            p = order.permutation(self.prompt_lens)
            o = order.permutation(self.output_lens)
            det = order.permutation(np.arange(k) < self.n_det)
            for j in range(k):
                prompt = tokens.integers(0, self.vocab, int(p[j])).tolist()
                yield RequestSpec(i, prompt, int(o[j]), bool(det[j]))
                i += 1


def make(mix: Dict, seed: int, vocab_size: int) -> ClosedLoop:
    return ClosedLoop(mix, seed, vocab_size)
