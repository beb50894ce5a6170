"""Seeded inputs, made on the device in one jitted call.

The benchmark, not the program, makes the data: the plain references read the
same array the program is handed, and nothing here imports the program. The
PRNG key is an operand of the jitted generator, so every seed runs the same
compiled program (one entry in the persistent compilation cache).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number. The driver's seeds pass
    2**31, which ``PRNGKey`` refuses without 64-bit mode: fold the high bits in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@functools.lru_cache(maxsize=None)
def _normal_program(shape, loc, scale, sharding):
    def gen(key):
        return loc + scale * jax.random.normal(key, shape, jnp.float32)

    return jax.jit(gen, out_shardings=sharding)


def normal(seed: int, shape, loc: float, scale: float, sharding=None, stream: int = 0) -> jax.Array:
    """f32 ``loc + scale * N(0, 1)`` of ``shape``, born under ``sharding`` (each
    device draws its own shard: the Threefry generator is partitionable).
    ``stream`` separates several arrays drawn from one seed."""
    key = jax.random.fold_in(key_from_seed(seed), stream)
    return _normal_program(tuple(int(s) for s in shape), float(loc), float(scale), sharding)(key)
