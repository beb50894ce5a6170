"""One k-means fit by Lloyd's algorithm: every iteration must read the rows
once (the assignment and the update can share the read, and the centres
change between iterations, so no two iterations can), and the labels are
written once. FLOP are 4 per row, feature and cluster and iteration (2 for
the distances, 2 for the one-hot sums). At 16 features and 8 clusters that is
128 FLOP per 64-byte row, 2 FLOP a byte against the chip's 240: HBM-bound by
a factor of about 30 even if every multiplication took six bfloat16 passes
(120). The transposed copy, sum|x|^2 and repeated label passes are
implementation traffic and are not counted."""

from __future__ import annotations

from chipbench.rooflines import least


def per_op(config: dict, traffic: dict, results_per_op: int, peaks: dict) -> dict:
    rows, f, k, iters = (float(config[key]) for key in ("rows_per_chip", "features", "n_clusters", "max_iter"))
    return least(iters * rows * f * 4.0 + rows * 4.0, iters * 4.0 * rows * f * k, peaks)
