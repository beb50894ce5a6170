"""Mean of the benchmark's ``bench.record`` spans (the ``ht.*`` call that
records one result's chain), microseconds per forced result."""


def read(run):
    mean_s = run.trace.span_mean_s("bench.record")
    return None if mean_s is None else 1e6 * mean_s
