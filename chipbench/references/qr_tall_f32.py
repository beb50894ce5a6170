"""Plain reference for the ``qr_tall_f32`` configuration: the reduced QR
factorisation of a tall float32 operand by Householder reflections, in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``. No Gram matrix, no Cholesky
factor, no kernel, no engine: nothing the program's CholeskyQR2 route could
share. It imports nothing of the program and is handed only the rows.

Where it departs from a textbook Householder QR of the whole operand:

* ``r_factor`` takes the rows in blocks. A Householder QR of all 1 250 000 x
  512 rows at once holds the operand, its reflectors and the compiler's
  copies (with Q, compiled for a v5e: 5.8 GB beside the operand's 2.56),
  and the run still keeps the operand; so each block of ``block_rows`` rows
  is factored alone (``jnp.linalg.qr(block, mode="r")``), the (n, n) factors
  are stacked, as many as a block holds, and the stacks are factored again
  until one factor is left. R of the stack is R of the operand: the blocks'
  orthogonal factors make one orthogonal factor together.
* A short last block and a short stack are filled up with rows of zeros to
  ``block_rows`` rows. A row of zeros adds nothing to A^T A, whose Cholesky
  factor R is, so R stays what it was; and every factorisation has one
  shape, which the TPU's compiler takes a quarter of a minute to compile.
* The signs are turned so that R's diagonal is positive: the reduced QR of a
  full-rank operand is unique only up to the sign of each row of R (and of
  the matching column of Q), Householder leaves them mixed, and the
  comparison needs one convention on both sides.
* Q is never formed whole (2.56 GB more). ``q_rows`` gives the rows of Q the
  comparison samples as ``a_rows R^-1``, solved on the host in float64:
  rows of A = Q R, so each row of Q is its row of A against R alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 25_600  # 49 blocks of the cell's 1 250 000 rows, and one stack of their 49 factors of 512 rows


def positive_diagonal(r):
    """``r`` with each row's sign turned so that its diagonal entry is
    positive (a zero stays)."""
    sign = jnp.where(jnp.diagonal(r) < 0, -1.0, 1.0).astype(r.dtype)
    return sign[:, None] * r


def _r_of(rows, block_rows: int):
    """R of at most ``block_rows`` rows, filled up with rows of zeros."""
    short = block_rows - rows.shape[0]
    return jnp.linalg.qr(jnp.pad(rows, ((0, short), (0, 0))) if short else rows, mode="r")


def r_factor(a, block_rows: int = BLOCK_ROWS):
    """The (n, n) upper-triangular float32 R of the (m, n) operand ``a``,
    its diagonal positive."""
    m, n = a.shape
    per_stack = block_rows // n
    if per_stack < 2:
        raise ValueError(f"block_rows {block_rows} holds fewer than two factors of {n} rows")
    with jax.default_matmul_precision("highest"):
        factors = [
            _r_of(jax.lax.dynamic_slice_in_dim(a, start, min(block_rows, m - start), axis=0), block_rows)
            for start in range(0, m, block_rows)
        ]
        while len(factors) > 1:
            factors = [_r_of(jnp.concatenate(factors[i : i + per_stack]), block_rows) for i in range(0, len(factors), per_stack)]
        return positive_diagonal(factors[0])


def q_rows(a_rows, r) -> np.ndarray:
    """The rows of Q that belong to the rows ``a_rows`` of the operand:
    ``a_rows R^-1`` in float64 on the host (R is upper triangular; its
    transpose is solved against the rows as columns)."""
    a_rows, r = np.asarray(a_rows, np.float64), np.asarray(r, np.float64)
    return np.linalg.solve(r.T, a_rows.T).T
