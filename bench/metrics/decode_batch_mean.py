"""Sequences per decode launch in the window (tokens from decode
launches over decode launches, from the launch log)."""


def read(run):
    dec = [e for e in run.launches or () if e["kind"] == "decode"
           and run.t0 <= e["t"] <= run.t1]
    if not dec:
        return None
    return sum(len(e["ctx"]) for e in dec) / len(dec)
