"""The one traffic generator: turns a traffic file's parameters and a seed
into requests.

Every seed and every batch get the same set of requests' sizes in
another order, so a seed changes which tokens are sent and in which slot,
never how much work a batch holds.  A batch's prompt and output lengths
are the quantiles ``(i + 0.5) / n`` of a lognormal with the file's median
(or mean) and sigma, rounded and clipped.  Prompt lengths are paired with
output lengths by a permutation fixed by the file's ``pairing_seed``; the
run's seed orders the pairs and draws the token ids uniformly over the
vocabulary.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Request:
    """One request as the client sends it."""

    rid: int
    prompt: tuple[int, ...]
    max_new_tokens: int


def lognormal_median(spec: dict) -> float:
    """The median of ``spec``'s lognormal: given, or from its mean."""
    if "median" in spec:
        return float(spec["median"])
    return float(spec["mean"]) * math.exp(-float(spec["sigma"]) ** 2 / 2)


def lognormal_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the quantiles of ``spec``'s clipped lognormal."""
    median, sigma = lognormal_median(spec), float(spec["sigma"])
    lo, hi = int(spec["min"]), int(spec["max"])
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(min(hi, max(lo, int(round(median * math.exp(sigma * z))))))
    return out


def batch_rng(seed: int, index: int) -> np.random.Generator:
    # SeedSequence takes any non-negative int, however large
    return np.random.default_rng([int(seed), int(index)])


def closed_batch(traffic: dict, *, seed: int, index: int, vocab: int,
                 first_rid: int = 0) -> list[Request]:
    """Batch ``index`` of a closed-loop batch client."""
    n = int(traffic["batch"])
    prompts = lognormal_lengths(traffic["prompt"], n)
    outputs = lognormal_lengths(traffic["output"], n)
    pairing = np.random.default_rng(int(traffic.get("pairing_seed", 0))).permutation(n)
    pairs = [(prompts[i], outputs[j]) for i, j in enumerate(pairing)]
    rng = batch_rng(seed, index)
    return [Request(rid=first_rid + i,
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, vocab, size=int(pairs[k][0]))),
                    max_new_tokens=int(pairs[k][1]))
            for i, k in enumerate(rng.permutation(n))]


def steps_of(req: Request) -> int:
    """Decode steps a request takes from a fresh state: one per prompt
    token, then one per new token but the first, which the last prompt
    step already yields."""
    return len(req.prompt) + req.max_new_tokens - 1


def longest_sequence(traffic: dict) -> int:
    """Cache positions the longest request of this mix can fill."""
    return int(traffic["prompt"]["max"]) + int(traffic["output"]["max"]) - 1
