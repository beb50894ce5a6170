"""One lasso fit by coordinate descent on the n rows of m features one device
of ``chips`` holds: the least ANY implementation needs. The rows and the
labels are read once, 4 n m + 4 n bytes (a fit that keeps the second moments
G = X^T X and cy = X^T y needs the rows no more; the m x m matrix itself, 1 MB,
is not counted); the FLOP are the Gram's, 2 n m^2, and 2 m^2 a sweep for the
``max_iter`` sweeps of m coordinate steps on an m-vector, at the chip's
bfloat16 rate whatever passes a float32 product takes. At 512 features that
is 256 FLOP a byte against the chip's 240: compute-bound by the count, barely.
The six bfloat16 passes of a float32 product, the Gram's lower triangle, a
second read of the rows for cy, a transposed copy, and above all the LATENCY
of the 512 serial steps of a sweep (each waits for the last) are
implementation work and are not counted."""

from __future__ import annotations

from chipbench.rooflines import least


def per_op(config: dict, chips: int, peaks: dict) -> dict:
    n, m = float(config["rows"][str(chips)]) / chips, float(config["features"])
    return least(n * m * 4.0 + n * 4.0, 2.0 * n * m * m + 2.0 * float(config["max_iter"]) * m * m, peaks)
