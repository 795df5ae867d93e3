"""Percentiles over every sample of a run.

A latency tail is taken over all requests: a request that failed or never
produced what was timed is in the sample as ``math.inf`` (it missed every
limit), so a tail that reaches it reads ``inf``.
"""
from __future__ import annotations

import math
from typing import Iterable, List


def percentile(values: Iterable[float], p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``p``%
    of the samples at or below it.  ``inf`` entries count as samples."""
    xs: List[float] = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    return xs[max(1, math.ceil(p / 100.0 * len(xs))) - 1]



def ttft_ms(recs: Iterable, t1: float) -> List[float]:
    """First token minus due time (ms) of every request due by ``t1``;
    one that failed, was refused, or has no first token is ``inf``."""
    out = []
    for r in recs:
        if r.due > t1:
            continue
        missed = math.isnan(r.first) or bool(r.error)
        out.append(math.inf if missed else (r.first - r.due) * 1e3)
    return out
