"""Share of the traced stretch in which a collective runs on a chip and
no other operation does (``trace_reduce``'s ``collective_exposed_s``),
averaged over the chips used.  A run on one chip, or a trace with no
device plane, gives nothing."""


def read(run):
    if run.trace is None or run.chips < 2 \
            or not run.trace.collective_exposed_s:
        return None
    exposed = run.trace.collective_exposed_s.values()
    return 100.0 * sum(exposed) / len(exposed) / run.trace.window_s
