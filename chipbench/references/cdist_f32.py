"""Plain reference for the ``cdist_f32`` configuration: Euclidean distances by
direct differences, in float64 on the host.

For each sampled row ``i`` the distance to every row ``j`` of ``x`` is
``sqrt(sum((x_i - x_j)**2))`` over the features: difference, square, sum,
root. No quadratic expansion and no matrix product, so nothing cancels and
nothing the MXU could round; the diagonal is exactly 0. One sampled row is one
block: its (n, f) differences fit any host, and the blocks are independent, so
a few threads take them side by side (numpy releases the interpreter lock).
It imports nothing of the program and is handed only the rows.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8


def rows(x, idx) -> np.ndarray:
    """The (len(idx), n) float64 distances of rows ``idx`` of the (n, f)
    array ``x`` to all of its rows."""
    x = np.asarray(x, np.float64)

    def one(i):
        diff = x - x[int(i)]
        return np.sqrt(np.einsum("jf,jf->j", diff, diff))

    with ThreadPoolExecutor(THREADS) as pool:
        return np.stack(list(pool.map(one, idx)))
