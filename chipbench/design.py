"""A seeded regression problem, made on the device in one jitted call: the
design matrix of the ``lasso_f32`` configuration and its labels. Beside
``datagen.normal`` and on its terms: the benchmark makes the data, nothing
here imports the program, and the PRNG key is an operand, so every seed runs
the same compiled program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.datagen import key_from_seed


@functools.lru_cache(maxsize=None)
def _design_program(n, m, block, loc, rho, noise, sharding, y_sharding):
    # draw j goes to the columns by row j of `mix`: columns 1.. are (loc + Z B) / sqrt(loc^2 + 1) with
    # B^T B the AR(1) correlation matrix (B^T its Cholesky factor); column 0 takes no draw and stays at 1
    k = np.arange(m - 1)
    unit = 1.0 / np.sqrt(loc * loc + 1.0)
    mix = np.zeros((m, m), np.float32)
    mix[1:, 1:] = unit * np.linalg.cholesky(float(rho) ** np.abs(k[:, None] - k[None, :])).T
    centre = np.full(m, unit * loc, np.float32)
    centre[0] = 1.0

    def gen(key, theta):
        def block_of(i):
            kz, ke = jax.random.split(jax.random.fold_in(key, i))
            z = jax.random.normal(kz, (block, m), jnp.float32)
            x = centre + jnp.matmul(z, mix, precision=jax.lax.Precision.HIGHEST)
            y = jnp.matmul(x, theta, precision=jax.lax.Precision.HIGHEST)
            return x, y + noise * jax.random.normal(ke, (block,), jnp.float32)

        x, y = jax.lax.map(block_of, jnp.arange(n // block, dtype=jnp.int32))
        return x.reshape(n, m), y.reshape(n, 1)

    return jax.jit(gen, out_shardings=(sharding, y_sharding))


def correlated_design(seed: int, shape, loc: float, rho: float, theta, noise: float, block_rows: int, sharding=None, y_sharding=None):
    """f32 ``x`` (n, m) and ``y`` (n, 1) from ``seed``, made in blocks of
    ``block_rows`` rows (a divisor of n) so that no second operand-sized array
    is alive. Column 0 of ``x`` is all ones (the intercept); the others are
    ``(loc + Z B) / sqrt(loc^2 + 1)``, Z i.i.d. N(0, 1) and ``B^T B`` the
    AR(1) correlation matrix ``rho^|j-k|``: off-centre, neighbours correlated,
    and of unit mean square, which upstream's coordinate step takes for
    granted (it sets ``theta_j = soft(rho_j)`` without dividing by the
    column's mean square; upstream's demo divides each column by its root
    mean square first; on columns of mean square 2 every step overshoots and
    the iterates grow without bound: what refused PR 39). ``y = x theta + noise * eps`` for the (m,) coefficients ``theta``,
    eps i.i.d. N(0, 1)."""
    n, m = (int(s) for s in shape)
    block = min(int(block_rows), n)
    if n % block:
        raise ValueError(f"block_rows {block} does not divide the {n} rows")
    program = _design_program(n, m, block, float(loc), float(rho), float(noise), sharding, y_sharding)
    return program(key_from_seed(seed), jnp.asarray(theta, jnp.float32))
