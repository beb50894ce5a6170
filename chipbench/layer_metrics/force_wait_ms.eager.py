"""Mean of the benchmark's ``bench.force`` spans (force -> dispatch ->
blocking read of one result), milliseconds per forced result."""


def read(run):
    mean_s = run.trace.span_mean_s("bench.force")
    return None if mean_s is None else 1e3 * mean_s
