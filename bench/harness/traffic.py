"""The one traffic generator. A mix is a data file
(``bench/traffic/<mix>.json``); this module turns it and a seed into
requests.

Sizes and gaps are drawn in blocks of ``block`` requests by stratified
inverse-CDF sampling, so every block holds the mix's proportions
exactly, and shuffled by a stream that is the same for every seed: a
mix has one schedule of sizes and arrivals. The run's seed draws the
prompt ids (and, elsewhere, the weights). Runs on different seeds then
do the same work, and the seed does not move a tail by reordering a few
long requests.

Distributions a mix may name:

- ``{"choices": [...], "weights": [...]}``: a categorical length;
- ``{"lognormal_median": m, "sigma": s, "min": a, "max": b}``: a
  lognormal length, rounded and clipped to ``[a, b]``;
- arrivals ``{"kind": "offline"}`` (a queue that never runs dry) or
  ``{"kind": "poisson", "rate_per_s": r}`` (exponential gaps, open loop).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One generated request: ``due`` is seconds after the window opens
    (0 for an offline queue)."""

    index: int
    prompt: tuple
    max_new: int
    due: float


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one seed; any whole
    number is a seed."""
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64,
                                                        stream]))


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lengths(dist: dict, block: int) -> np.ndarray:
    """The block's multiset of lengths (sorted), from the stratified
    quantiles of ``dist``."""
    u = _quantiles(block)
    if "choices" in dist:
        w = np.asarray(dist["weights"], float)
        cdf = np.cumsum(w / w.sum())
        idx = np.searchsorted(cdf, u, side="right")
        return np.asarray(dist["choices"], np.int64)[idx]
    if "lognormal_median" in dist:
        z = np.asarray([statistics.NormalDist().inv_cdf(p) for p in u])
        x = dist["lognormal_median"] * np.exp(dist["sigma"] * z)
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist}")


def gaps(arrivals: dict, block: int) -> np.ndarray:
    """The block's inter-arrival gaps in seconds (sorted)."""
    if arrivals["kind"] == "offline":
        return np.zeros(block)
    if arrivals["kind"] == "poisson":
        u = _quantiles(block)
        return -np.log1p(-u) / float(arrivals["rate_per_s"])
    raise ValueError(f"unknown arrivals {arrivals}")


class Generator:
    """Yields :class:`Planned` requests of one mix for one seed, forever
    (the caller stops at its window's end)."""

    def __init__(self, mix: dict, seed: int, vocab: int, *, stream: int = 0):
        self.mix = mix
        self.vocab = int(vocab)
        self.block = int(mix.get("block", 64))
        self._schedule = rng(0, 1000 + stream)
        self._ids = rng(seed, 2000 + stream)
        self._prompt_lens = lengths(mix["prompt_len"], self.block)
        self._output_lens = lengths(mix["output_len"], self.block)
        self._gaps = gaps(mix["arrivals"], self.block)
        self._i = 0
        self._t = 0.0
        self._pending: list = []

    def _refill(self):
        r = self._schedule
        p = r.permutation(self._prompt_lens)
        o = r.permutation(self._output_lens)
        g = r.permutation(self._gaps)
        for k in range(self.block):
            self._t += float(g[k])
            prompt = tuple(int(t) for t in
                           self._ids.integers(0, self.vocab, int(p[k])))
            self._pending.append(
                Planned(self._i, prompt, int(o[k]), self._t)
            )
            self._i += 1
        self._pending.reverse()

    def __iter__(self):
        return self

    def __next__(self) -> Planned:
        if not self._pending:
            self._refill()
        return self._pending.pop()


def max_length(dist: dict) -> int:
    if "choices" in dist:
        return int(max(dist["choices"]))
    return int(dist["max"])
