"""Process start to window start (host clock): loading, weights,
compiling or loading every program from the cache, and warming up."""


def read(run):
    return run.setup_s
