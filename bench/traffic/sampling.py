"""Seeded, stratified draws shared by the traffic kinds.

A length or a gap is read from its distribution at evenly spaced
probabilities, and the seed only shuffles them: every seed then gives
the same set of sizes and arrivals in another order, so runs on
different seeds do the same work. Token ids are uniform from the seed.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist


def _quantiles(n: int) -> list[float]:
    return [(i + 0.5) / n for i in range(n)]


def lognormal(n: int, median: float, sigma: float, lo: int, hi: int,
              rng: random.Random) -> list[int]:
    """n whole numbers from a lognormal (``median``, ``sigma``) read at
    evenly spaced probabilities, clipped to [lo, hi], in seeded order."""
    z = NormalDist()
    xs = [min(hi, max(lo, round(median * math.exp(sigma * z.inv_cdf(u)))))
          for u in _quantiles(n)]
    rng.shuffle(xs)
    return xs


def exponential_gaps(n: int, rate: float, rng: random.Random) -> list[float]:
    """n gaps of a Poisson process at ``rate`` read at evenly spaced
    probabilities, in seeded order."""
    gaps = [-math.log(1.0 - u) / rate for u in _quantiles(n)]
    rng.shuffle(gaps)
    return gaps


def token_ids(n: int, vocab: int, rng: random.Random) -> list[int]:
    """n ids uniform over [1, vocab)."""
    return [rng.randrange(1, vocab) for _ in range(n)]


def rng_for(seed: int, stream: str) -> random.Random:
    """An independent generator per (seed, stream)."""
    return random.Random(f"{int(seed)}:{stream}")
