"""Forcing points of the fusion engine per op (delta of ``cache_stats()['forces']``)."""


def read(run):
    if not run.attempted:
        return None
    forces = run.counters["after"]["fusion"]["forces"] - run.counters["before"]["fusion"]["forces"]
    return forces / run.attempted
