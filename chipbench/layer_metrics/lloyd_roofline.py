"""Least time the chip needs for one fit (one read of the rows per Lloyd
iteration and the labels written once, ``rooflines/lloyd.py``; HBM-bound)
over ALL the device's busy time per fit in the trace, whatever implements
the fit: kernel, transposes, sum|x|^2, label epilogues."""

from chipbench import rooflines
from chipbench.rooflines import lloyd


def read(run):
    busy = run.trace.busy_in_ops_per_op()
    if busy <= 0:
        return None
    least = lloyd.per_op(run.config, run.traffic, run.results_per_op, rooflines.peaks(run.device_kind))
    return 100.0 * least["seconds"] / busy
