"""99th percentile over every gap between successive tokens of one
request whose later token was streamed inside the window (host clock,
ms).  A stall or a preemption shows in it."""
from stats import percentile


def read(run):
    gaps = [(b - a) * 1e3 for r in run.recs.values()
            for a, b in zip(r.times, r.times[1:]) if run.t0 <= b <= run.t1]
    return percentile(gaps, 99) if gaps else None
