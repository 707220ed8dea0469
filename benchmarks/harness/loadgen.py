"""Seeded traffic for generation cells: one general generator that a
traffic file parameterises.  Everything here is a pure function of the
file and the seed — no clock, no global state — so the same seed gives
the same requests and the same schedule.

A traffic file's `requests` section::

    "requests": {
      "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
      "output_len": {"median": 64, "sigma": 0.7, "min": 16, "max": 256}
    }

Lengths are clipped log-normals, drawn in stratified blocks so that
every seed offers nearly the same multiset of lengths.

and its `arrivals` section, one of::

    {"process": "open", "rate_per_s": 1.8}
    {"process": "closed", "clients": 16}

An open loop sends exactly round(rate x seconds) requests, as a Poisson
process conditioned on its count.
"""
from statistics import NormalDist

import numpy as np


class Request:
    __slots__ = ("prompt", "budget")

    def __init__(self, prompt, budget):
        self.prompt = prompt
        self.budget = budget


def _rng(seed, stream):
    # independent streams per purpose, so changing how many arrivals are
    # drawn never changes the requests' contents
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


BLOCK = 64
_NORMAL = NormalDist()


def _stratified_lengths(rng, dist):
    """BLOCK lengths from the clipped log-normal `dist`, one from each
    of BLOCK equal slices of probability, in a seeded order: every block
    holds nearly the same multiset whatever the seed, so the work a
    window offers is fixed and only its order and pairing change."""
    u = (rng.permutation(BLOCK) + rng.random(BLOCK)) / BLOCK
    z = np.asarray([_NORMAL.inv_cdf(min(max(v, 1e-9), 1 - 1e-9)) for v in u])
    val = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(val), dist["min"], dist["max"]).astype(np.int64)


class RequestList:
    """The seed's endless list of requests, made in blocks of BLOCK:
    item i is the same however many are drawn, and in whatever order."""

    def __init__(self, spec, vocab, seed):
        self._spec, self._vocab, self._seed = spec, int(vocab), int(seed)
        self._blocks = {}

    def _block(self, b):
        if b not in self._blocks:
            rng = _rng(self._seed, 100000 + b)
            self._blocks[b] = (
                _stratified_lengths(rng, self._spec["prompt_len"]),
                _stratified_lengths(rng, self._spec["output_len"]))
        return self._blocks[b]

    def __getitem__(self, i):
        plens, olens = self._block(i // BLOCK)
        toks = _rng(self._seed, 1000 + i).integers(
            0, self._vocab, int(plens[i % BLOCK]))
        return Request(toks.astype(np.int32), int(olens[i % BLOCK]))


def arrival_times(rate_per_s, horizon_s, seed):
    """Due times of an open loop at `rate_per_s` over [0, horizon_s):
    exactly round(rate x horizon) of them, so every seed offers the same
    number of requests.  Gaps are exponential, then scaled so that the
    gap after the last arrival ends at the horizon: a Poisson process
    conditioned on its count."""
    n = max(1, int(round(rate_per_s * horizon_s)))
    gaps = _rng(seed, 3).exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] * (horizon_s / gaps.sum())


def quantile(values, q):
    """Linear-interpolated quantile of a non-empty sequence."""
    return float(np.quantile(np.asarray(values, np.float64), q))
