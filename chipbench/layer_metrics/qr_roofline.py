"""Least time the chip needs for one reduced QR with Q (the rows read once,
Q written once, one product's FLOP, ``rooflines/qr.py``; HBM-bound) over ALL
the busiest device's busy time per op in the trace, whatever implements the
op: Grams, Cholesky factorisations, triangular solves, the products that form
Q1 and Q, copies, the rows sampled for the check."""

from chipbench import rooflines
from chipbench.rooflines import qr


def read(run):
    busy = run.trace.busy_in_ops_per_op()
    if busy <= 0:
        return None
    return 100.0 * qr.per_op(run.config, run.chips, rooflines.peaks(run.device_kind))["seconds"] / busy
