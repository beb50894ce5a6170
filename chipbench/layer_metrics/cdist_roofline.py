"""Least time the chip needs for one distance matrix (its rows of the result
written once, the operand read once, ``rooflines/cdist.py``; HBM-bound) over
ALL the busiest device's busy time per op in the trace, whatever implements
the op: products, epilogues, copies into place, the rows sampled for the
check."""

from chipbench import rooflines
from chipbench.rooflines import cdist


def read(run):
    busy = run.trace.busy_in_ops_per_op()
    if busy <= 0:
        return None
    return 100.0 * cdist.per_op(run.config, run.chips, rooflines.peaks(run.device_kind))["seconds"] / busy
