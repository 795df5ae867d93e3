"""Share of the traced stretch in which no program ran on the device,
averaged over the chips used (device trace, ``XLA Modules``)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
