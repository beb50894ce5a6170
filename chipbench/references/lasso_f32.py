"""Plain reference for the ``lasso_f32`` configuration: upstream Heat's
coordinate descent (``heat/regression/lasso.py:90-141``) in straightforward
``jax.numpy`` / NumPy on float32 data under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program and is handed only the rows, the labels, ``lam`` and the sweeps.

``fit_residual`` is upstream's loop as written: from theta = 0, for each sweep
and for j = 0..m-1 in order, the whole estimate ``x @ theta``, then ``rho =
mean(x_j * (y - x theta + theta_j x_j))``, theta_0 = rho (the first column is
the intercept and is not penalised), theta_j = soft(rho, lam) otherwise. Every
step reads all the rows: m * sweeps passes, which only a small operand bears.

``fit_blocks`` gives the same iterates at the cell's size. Where it departs
from upstream's loop:

* The rows are read ONCE. ``rho`` is linear in the rows' second moments:
  ``n rho_j = cy_j - sum_i G_ji theta_i + G_jj theta_j`` with ``G = x^T x`` and
  ``cy = x^T y`` (upstream's expression multiplied out; sklearn's
  ``precompute=True``). So the 15 360 coordinate steps of the cell run on the
  512 x 512 matrix, not on 6.4 GB.
* ``G``, ``cy`` and ``y^T y`` are summed over blocks of ``block_rows`` rows:
  each block's products in float32 on the device, the blocks added up in
  float64 on the host, as the k-means reference finishes its long sums. A
  short last block is filled up with rows of zeros, which add nothing to any
  of the three.
* Inside a block no product holds more than ``SUM_ROWS`` rows; a block's
  products are added up one by one in float32. On a TPU the float32 product's
  accumulator rounds towards zero, once for every 128 rows of the
  contraction: a Gram of positive terms is 1.3e-6 low in sums of 32 768 rows
  and 7.4e-5 in one of 3 145 728, unevenly, and theta is thirty to fifty times
  as sensitive as the moments it is made from (v5e, PERF.md PR 40). Over 1 024
  rows the loss is float32's own last bit, and a float32 sum of 32 such
  products, rounded to nearest, adds no more.
* The coordinate steps run in float64 NumPy on the host, on the m-vector
  ``c = cy - G theta`` kept up to date after each step (``c -= (new -
  theta_j) G_j``) and made afresh at the start of each sweep.
* ``moments`` is kept apart so that the comparison can price any theta by the
  objective ``1/2 mean((y - x theta)^2) + lam |theta_1:|_1`` from the same
  three sums (``objective``), never from the rows again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 15  # 96 blocks of the cell's 3 145 728 rows: 96 Grams of 1 MB
SUM_ROWS = 1 << 10  # rows of one product: what a float32 accumulator on the MXU holds without loss


def soft(rho, lam):
    """The soft threshold: ``rho`` drawn towards zero by ``lam``."""
    return np.sign(rho) * np.maximum(np.abs(rho) - lam, 0.0)


def fit_residual(x, y, lam: float, sweeps: int) -> np.ndarray:
    """Upstream's equations as written, float32; theta (m,) after ``sweeps``
    sweeps from zero."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32).reshape(-1)
    theta = jnp.zeros(x.shape[1], jnp.float32)
    with jax.default_matmul_precision("highest"):
        for _ in range(sweeps):
            for j in range(x.shape[1]):
                x_j = x[:, j]
                rho = jnp.mean(x_j * (y - x @ theta + theta[j] * x_j))
                theta = theta.at[j].set(rho if j == 0 else jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0))
    return np.asarray(theta)


@functools.partial(jax.jit, static_argnames=("block",))
def _block_sums(x, y, block: int):
    """Per block of ``block`` rows: x_b^T x_b, x_b^T y_b and y_b^T y_b."""
    n = x.shape[0]
    short = -n % block
    if short:
        x, y = jnp.pad(x, ((0, short), (0, 0))), jnp.pad(y, (0, short))

    sub = SUM_ROWS if block % SUM_ROWS == 0 else block
    m = x.shape[1]

    def one(start):
        def part(i, sums):
            xb = jax.lax.dynamic_slice_in_dim(x, start + i * sub, sub, axis=0)
            yb = jax.lax.dynamic_slice_in_dim(y, start + i * sub, sub, axis=0)
            return sums[0] + xb.T @ xb, sums[1] + xb.T @ yb, sums[2] + yb @ yb

        zeros = jnp.zeros((m, m), jnp.float32), jnp.zeros(m, jnp.float32), jnp.zeros((), jnp.float32)
        return jax.lax.fori_loop(0, block // sub, part, zeros)

    return jax.lax.map(one, jnp.arange(0, n + short, block, dtype=jnp.int32))


def moments(x, y, block_rows: int = BLOCK_ROWS):
    """``(G, cy, yy, n)``: x^T x (m, m), x^T y (m,) and y^T y in float64, each
    a float64 sum of per-block float32 products, and the row count."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32).reshape(-1)
    with jax.default_matmul_precision("highest"):
        g, cy, yy = _block_sums(x, y, min(int(block_rows), x.shape[0]))
    return tuple(np.asarray(part, np.float64).sum(axis=0) for part in (g, cy, yy)) + (x.shape[0],)


def descend(g: np.ndarray, cy: np.ndarray, n: int, lam: float, sweeps: int) -> np.ndarray:
    """``sweeps`` sweeps of coordinate descent from zero on the second
    moments, float64: upstream's order, upstream's intercept."""
    theta = np.zeros(len(cy))
    for _ in range(sweeps):
        c = cy - g @ theta
        for j in range(len(theta)):
            rho = (c[j] + theta[j] * g[j, j]) / n
            new = rho if j == 0 else soft(rho, lam)
            c -= (new - theta[j]) * g[j]
            theta[j] = new
    return theta


def fit_blocks(x, y, lam: float, sweeps: int, block_rows: int = BLOCK_ROWS) -> np.ndarray:
    """The iterates of :func:`fit_residual` at any size: theta (m,) float64."""
    g, cy, _, n = moments(x, y, block_rows)
    return descend(g, cy, n, lam, sweeps)


def objective(theta, g, cy, yy, n: int, lam: float) -> float:
    """``1/2 mean((y - x theta)^2) + lam |theta_1:|_1`` from the second
    moments, float64."""
    theta = np.asarray(theta, np.float64).reshape(-1)
    return float(0.5 * (yy - 2.0 * theta @ cy + theta @ g @ theta) / n + lam * np.abs(theta[1:]).sum())
