"""Traffic generation from a mix's data file and a seed.

One general generator reads every mix.  A mix file names the loop (open or
closed), the arrival process, and the prompt and output length
distributions; an open-loop cell's rate lives in a per-cell rate file.

Sampling is stratified in blocks: each block of ``block`` requests takes
the value at the middle of each of ``block`` equal-probability strata of
every distribution, in a seeded order.  Every seed then offers the same
sizes and gaps, only in another order, so a run's work does not swing with
the seed.  The highest value a block offers is the distribution's
``1 - 1 / (2 * block)`` quantile; a larger block reaches further into the
tail.

A mix may fix that order too, with ``sizes_seed``: sizes (and an open
loop's gaps) are then drawn from it and not from the run's seed, so every
run offers the same sequence of sizes, and the run's seed draws only the
token ids.  A closed loop whose window holds about one block needs it:
there the order decides which sizes fall inside the window, and so how
much work a run does.

The Poisson arithmetic follows ``repro.serving.workload`` (exponential
inter-arrival gaps at the offered rate); it is copied here so that a change
to the program cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Request:
    """One request of a run: due ``t`` seconds after traffic starts (open
    loop; a closed loop ignores ``t``)."""
    t: float
    prompt: np.ndarray
    max_new_tokens: int


def _quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    if kind == "uniform":
        # whole numbers lo..hi, each with probability 1 / (hi - lo + 1)
        lo, hi = dist["min"], dist["max"]
        return lo + math.floor(u * (hi - lo + 1))
    if kind == "exponential":
        return -math.log1p(-u) * dist["mean"]
    raise ValueError(f"unknown distribution {kind!r}")


def _midpoints(dist: dict, block: int) -> np.ndarray:
    return np.array([_quantile(dist, (i + 0.5) / block)
                     for i in range(block)])


def stratified(dist: dict, n: int, rng: np.random.Generator,
               block: int) -> np.ndarray:
    """``n`` values of ``dist``: per block, the middle of each of ``block``
    strata of probability, shuffled within the block."""
    vals = _midpoints(dist, block)
    out = np.empty(n, np.float64)
    for b0 in range(0, n, block):
        k = min(block, n - b0)
        out[b0:b0 + k] = rng.permutation(vals)[:k]
    return out


def lengths(dist: dict, n: int, rng: np.random.Generator,
            block: int) -> np.ndarray:
    """Whole-token lengths, clipped to the distribution's ``[min, max]``."""
    x = np.rint(stratified(dist, n, rng, block))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def poisson_times(rate_rps: float, n: int, rng: np.random.Generator,
                  block: int) -> np.ndarray:
    """``n`` ascending arrival times of a Poisson process at ``rate_rps``
    (stratified exponential gaps: each block of ``block`` arrivals spans
    the same time), from t = 0."""
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    unit = {"dist": "exponential", "mean": 1.0}
    # the middles of the strata fall short of the tail's mean: scale them
    # so that each block spans exactly block / rate_rps seconds
    scale = 1.0 / (rate_rps * _midpoints(unit, block).mean())
    return np.cumsum(stratified(unit, n, rng, block) * scale)


class RequestStream:
    """The run's requests, in order, drawn block by block from ``seed``.
    Open loop: ``t`` is the due time from traffic start (Poisson at
    ``rate_rps``).  Closed loop: ``t`` is 0 and clients take the next
    request as their last one completes.  Token ids are uniform over the
    model's whole vocabulary."""

    def __init__(self, mix: dict, seed: int, vocab: int,
                 rate_rps: Optional[float] = None):
        if mix["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        if mix["loop"] == "open" and not rate_rps:
            raise ValueError("an open loop needs a rate")
        self.mix, self.vocab, self.rate = mix, vocab, rate_rps
        self.block = int(mix.get("block", 16))
        self._rng = np.random.default_rng([mix.get("sizes_seed", seed), 7])
        self._tok = np.random.default_rng([seed, 11])
        self._buf: list[Request] = []
        self._t = 0.0

    def _refill(self):
        b, rng = self.block, self._rng
        plens = lengths(self.mix["prompt"], b, rng, b)
        outs = lengths(self.mix["output"], b, rng, b)
        if self.mix["loop"] == "open":
            times = self._t + poisson_times(self.rate, b, rng, b)
            self._t = float(times[-1])
        else:
            times = np.zeros(b)
        self._buf.extend(
            Request(float(times[i]),
                    self._tok.integers(0, self.vocab, size=int(plens[i]),
                                       dtype=np.int32),
                    int(outs[i])) for i in range(b))

    def peek(self) -> Request:
        if not self._buf:
            self._refill()
        return self._buf[0]

    def pop(self) -> Request:
        req = self.peek()
        del self._buf[0]
        return req
