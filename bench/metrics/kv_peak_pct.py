"""Peak KV pages in use in the window (the engine's ``kv_pages_peak``)
over the pool's usable pages.  Pages the prefix cache keeps count as in
use."""


def read(run):
    return 100.0 * run.counters.get("kv_pages_peak", 0) / run.usable_pages
