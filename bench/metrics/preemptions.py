"""Sequences preempted for pages in the window (the engine's
``pressure_preemptions_total``)."""


def read(run):
    return run.counters.get("pressure_preemptions_total", 0)
