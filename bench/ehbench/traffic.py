"""Request streams: Zipfian popularity and open-loop arrival schedules.

A schedule is one fixed set of requests (gaps between arrivals, and
bindings) drawn from the traffic mix's own ``schedule_seed``; a run's
seed draws only the order in which they come, so that every seed offers
the same work.

The Zipfian draw is YCSB's (Cooper et al., "Benchmarking Cloud Serving
Systems with YCSB", SoCC 2010): item ranks 1..n with probability
proportional to ``1 / rank**theta``, theta = 0.99 by default there.
"""
from __future__ import annotations

import numpy as np


def zipf_ranks(n: int, theta: float, size: int,
               rng: np.random.Generator) -> np.ndarray:
    """``size`` ranks in ``[0, n)`` (0 is the most popular), drawn by
    inverting the exact cumulative distribution."""
    if n < 1:
        raise ValueError("zipf over an empty population")
    weights = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      n - 1)


def arrival_times(rate: float, seconds: float, template: np.random.Generator,
                  order: np.random.Generator) -> np.ndarray:
    """Due times in ``[0, seconds)`` of ``round(rate * seconds)``
    requests: a Poisson process at ``rate`` given its count.  The gaps
    between arrivals are one set of exponential draws from ``template``,
    taken in an order drawn from ``order``, so every order offers the
    same gaps and ends at the same time."""
    n = int(round(rate * seconds))
    if n < 1 or seconds <= 0:
        return np.zeros(0)
    gaps = template.exponential(1.0, n + 1)[order.permutation(n + 1)]
    return seconds * np.cumsum(gaps)[:n] / gaps.sum()
