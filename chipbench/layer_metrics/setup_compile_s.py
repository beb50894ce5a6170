"""Seconds of the warm-up calls (first run: compilation; later: cache loads)."""


def read(run):
    return run.warm_up_s
