"""One reduced QR factorisation that returns Q, of the m rows of n columns
one device of ``chips`` holds: the least ANY implementation needs. The rows
of A are read once and the rows of Q written once, 4 m n bytes each, and R
(n, n) once; the FLOP are one full product's, 2 m n^2 (Householder and
modified Gram-Schmidt take that much for R with Q; CholeskyQR counts the same
with its symmetric Gram and its triangular product at half), at the chip's
bfloat16 rate whatever passes a float32 product takes. At 512 columns that is
128 FLOP a byte against the chip's 240: HBM-bound by the count. A second
orthogonalisation pass, the intermediate Q1, full Grams, the six bfloat16
passes of a float32 product and the small factorisations are implementation
work and are not counted."""

from __future__ import annotations

from chipbench.rooflines import least


def per_op(config: dict, chips: int, peaks: dict) -> dict:
    m, n = float(config["rows"][str(chips)]) / chips, float(config["columns"])
    return least(m * n * 4.0 + m * n * 4.0 + n * n * 4.0, 2.0 * m * n * n, peaks)
