"""Least time the chip needs for one trial's six reductions (one read of the
operand per forced result, ``rooflines/reduce.py``; HBM-bound) over the
device's busy time per trial in the trace."""

from chipbench import rooflines
from chipbench.rooflines import reduce


def read(run):
    busy = run.trace.busy_in_ops_per_op()
    if busy <= 0:
        return None
    least = reduce.per_op(run.config, run.traffic, run.results_per_op, rooflines.peaks(run.device_kind))
    return 100.0 * least["seconds"] / busy
