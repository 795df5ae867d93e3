"""Seeded draws for traffic generators.

Every seed gets the same multiset of sizes and gaps, in another order:
values are quantiles of the stated distribution at the midpoints of
``block`` equal strata, and each block of ``block`` consecutive draws is
a seeded permutation of them.  So any run of whole blocks holds the same
work whatever the seed.  A mix that fixes ``order_seed`` fixes the order
too, and the run's seed then changes only the token ids (and the
weights): every run does the same work.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np

_NORMAL = NormalDist()


def lognormal(spec: dict, n: int, block: int, rng: np.random.Generator
              ) -> List[int]:
    """``n`` whole numbers from a lognormal with ``median`` and
    ``sigma`` (of the log), clipped to ``[min, max]``."""
    qs = [spec["median"] * math.exp(spec["sigma"]
                                    * _NORMAL.inv_cdf((i + 0.5) / block))
          for i in range(block)]
    base = [int(min(spec["max"], max(spec["min"], round(q)))) for q in qs]
    return _blocks(base, n, rng)


def exponential_gaps(rate: float, n: int, block: int,
                     rng: np.random.Generator) -> List[float]:
    """``n`` gaps between Poisson arrivals at ``rate`` per second."""
    base = [-math.log(1.0 - (i + 0.5) / block) / rate for i in range(block)]
    return _blocks(base, n, rng)


def _blocks(base: list, n: int, rng: np.random.Generator) -> list:
    out: list = []
    while len(out) < n:
        out.extend(base[i] for i in rng.permutation(len(base)))
    return out[:n]


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int32)
