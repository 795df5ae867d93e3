"""Prompt tokens served from the prefix cache (the engine's
``prefix_hit_tokens_total`` in the window) over the prompt tokens of the
requests submitted in the window (a request refused at submission
brought none)."""


def read(run):
    submitted = sum(len(r.prompt) for r in run.recs.values()
                    if r.error != "rejected")
    if not submitted:
        return None
    return 100.0 * run.counters.get("prefix_hit_tokens_total", 0) / submitted
