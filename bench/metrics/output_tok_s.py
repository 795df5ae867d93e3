"""Tokens streamed to clients inside the window, over the window's
seconds (host clock)."""


def read(run):
    n = sum(1 for r in run.recs.values() for t in r.times
            if run.t0 <= t <= run.t1)
    return n / (run.t1 - run.t0)
