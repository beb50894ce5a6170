"""Least time the chip needs for one lasso fit (the rows and labels read
once, the Gram's FLOP and the sweeps', ``rooflines/lasso.py``; compute-bound
by the count) over ALL the busiest device's busy time per fit in the trace,
whatever implements the fit: the Gram's block products, the pass for cy, the
sweeps' serial steps, the programs of each sweep's change."""

from chipbench import rooflines
from chipbench.rooflines import lasso


def read(run):
    busy = run.trace.busy_in_ops_per_op()
    if busy <= 0:
        return None
    return 100.0 * lasso.per_op(run.config, run.chips, rooflines.peaks(run.device_kind))["seconds"] / busy
