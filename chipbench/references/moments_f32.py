"""Plain reference for the ``moments_f32`` configuration: ``mean`` and ``std``
(population, ddof=0) over axis None, 0 and 1 of a 2-D float32 array, two-pass,
with every long sum finished on the host in float64.

Rows are taken in blocks. Pass 1 returns each block's column sums and each
row's sum; the host adds them in float64 and forms the three means. Pass 2
returns the same sums of squared deviations from those means. It imports
nothing of the program and is handed only the input array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1024


def _block(n: int) -> int:
    return BLOCK_ROWS if n % BLOCK_ROWS == 0 else n


@functools.partial(jax.jit, static_argnames=("block",))
def _pass1(x, block: int):
    n, m = x.shape
    xb = x.reshape(n // block, block, m)
    return jnp.sum(xb, axis=1), jnp.sum(x, axis=1)  # (nb, m) column sums, (n,) row sums


@functools.partial(jax.jit, static_argnames=("block",))
def _pass2(x, mean_all, mean0, mean1, block: int):
    n, m = x.shape
    xb = x.reshape(n // block, block, m)
    d_all = xb - mean_all
    d0 = xb - mean0[None, None, :]
    d1 = x - mean1[:, None]
    return jnp.sum(d_all * d_all, axis=1), jnp.sum(d0 * d0, axis=1), jnp.sum(d1 * d1, axis=1)


def moments(x: jax.Array) -> dict:
    """The six results as float64 numpy: ``mean_all``, ``mean_0``, ``mean_1``,
    ``std_all``, ``std_0``, ``std_1``."""
    n, m = x.shape
    block = _block(n)
    col, row = _pass1(x, block)
    col = np.asarray(col, np.float64).sum(axis=0)
    row = np.asarray(row, np.float64)
    mean_all, mean0, mean1 = col.sum() / (n * m), col / n, row / m
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    s_all, s0, s1 = _pass2(x, f32(mean_all), f32(mean0), f32(mean1), block)
    return {
        "mean_all": np.float64(mean_all),
        "mean_0": mean0,
        "mean_1": mean1,
        "std_all": np.sqrt(np.asarray(s_all, np.float64).sum() / (n * m)),
        "std_0": np.sqrt(np.asarray(s0, np.float64).sum(axis=0) / n),
        "std_1": np.sqrt(np.asarray(s1, np.float64) / m),
    }
