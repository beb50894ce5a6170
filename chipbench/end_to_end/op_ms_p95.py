"""95th percentile of every op's wall time in the window, milliseconds."""

from chipbench.stats import percentile


def read(run):
    return 1e3 * percentile(run.op_s, 95)
