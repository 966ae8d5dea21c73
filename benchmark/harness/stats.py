"""Arrival and percentile arithmetic (the benchmark's own copy: the
yardstick does not move when the program's helpers do)."""

from __future__ import annotations

import math
import random
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over ALL values, q in (0, 1]; requests
    that never completed are passed in as +inf by the caller and so sit
    in the tail."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    rank = max(1, math.ceil(q * len(s)))
    return s[rank - 1]


def poisson_arrivals(rng: random.Random, rate_per_s: float,
                     seconds: float) -> List[float]:
    """Due times in [0, seconds) of a Poisson process. The count is
    fixed at round(rate x seconds) for every seed — the seed moves the
    arrivals, not the amount of work — by drawing that many uniform
    order statistics, which is the Poisson process conditioned on its
    count."""
    n = int(round(rate_per_s * seconds))
    return sorted(rng.random() * seconds for _ in range(n))


def rng_for(seed: int, what: str) -> random.Random:
    """A generator for one purpose from the run's seed (any whole
    number; Python's seeding takes arbitrary size)."""
    return random.Random("%d/%s" % (int(seed), what))
