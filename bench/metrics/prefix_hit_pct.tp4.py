"""Prompt tokens served from the prefix cache in the window (the
engine's ``prefix_hit_tokens_total``) over all the tokens the engine
took in for prefill there: those hits and the tokens its prefill
launches computed (``engine_prefill_tokens_total``).  Under a backlog
most submitted requests are still queued when the window closes, so the
prompt tokens submitted (``prefix_hit_pct``'s denominator) would count
requests the engine never saw.  A request prefilled again after a
recompute preemption counts again on both sides: its second hit and the
tokens it recomputes."""


def read(run):
    hit = run.counters.get("prefix_hit_tokens_total", 0)
    taken = hit + run.counters.get("engine_prefill_tokens_total", 0)
    if not taken:
        return None
    return 100.0 * hit / taken
