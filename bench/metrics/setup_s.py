"""Set-up: process start to the window's start, in seconds (weights,
engine, both programs compiled or read from the cache, the pre-roll)."""


def read(run):
    return run.setup_s
