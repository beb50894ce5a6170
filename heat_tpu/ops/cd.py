"""One coordinate-descent sweep of the lasso, as one Pallas kernel.

A sweep of Gram mode (``regression/lasso.py::lasso_cd_sweep``) is m serial
steps on the m-vector ``c = cy - G θ``: step j reads ``c[j]``, ``θ_j`` and
row j of G, sets ``θ_j`` and takes ``(new - θ_j) · G[j, :]`` from ``c``. As an
XLA ``while`` each step is an iteration of eight small operations, and the
loop's latency, not the chip's peak, sets its time (4.96 us a step on a v5e).
Here the whole sweep is one kernel that holds G in VMEM for its life and
keeps ``c`` and θ in registers (0.107 us a step on a v5e at m = 512):

- G (m, m) is held as m rows of (R, 128), R = m / 128 rounded up
  (:func:`hold`, once a fit): row j is one leading index, a (R, 128) slab,
  which is one vreg up to m = 1 024. Padded lanes hold zeros;
- ``c`` and θ are (R, 128) values of the loop: entry i lies at sublane
  i // 128, lane i % 128;
- ``lam`` and ``n`` are float32 scalars in SMEM.

A step picks ``c[j]``, ``θ_j`` and ``G[j, j]`` out of their vectors by a
masked sum (one entry and zeros: exact), so the step stays on the VPU and
never waits for a vector-to-scalar move. Its float32 operations are the
loop's, in the loop's order: ``rho = (c[j] + θ_j · G[j, j]) / n``, the soft
threshold (``rho`` itself for the unpenalised intercept, j = 0), ``c -= (new
- θ_j) · G[j, :]``, ``θ_j = new``. Row j is read, not column j: G is
symmetric to the bit. Coordinates from m up to the next 128 are never
stepped and leave the kernel as zeros.

:func:`takes` says, from what can be observed of G, whether the kernel is
its sweep: on TPUs, square, float32, and held within :data:`VMEM_BUDGET`.
Nothing here runs at import.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["VMEM_BUDGET", "held_bytes", "hold", "sweep", "takes"]

VMEM_BUDGET = 8 << 20
"""Bytes of VMEM that G, as :func:`hold` lays it out, may take: every m up to
1 024 (4 MiB there; 2 MiB at 512, whose R = 4 pads to a sublane tile of 8)."""

_LANE = 128
_SUBLANE = 8


def _rows(m: int) -> int:
    return -(-m // _LANE)


def held_bytes(m: int) -> int:
    """VMEM bytes of an (m, m) float32 G held as m rows of (R, 128), R
    padded to a whole sublane tile."""
    return m * (-(-_rows(m) // _SUBLANE) * _SUBLANE) * _LANE * 4


def takes(shape, dtype, sharding) -> bool:
    """Whether :func:`sweep` is the sweep for a Gram matrix of ``shape`` and
    ``dtype`` laid out by ``sharding``: its devices are TPUs, it is square and
    float32, and it is held within :data:`VMEM_BUDGET`."""
    return (
        all(d.platform == "tpu" for d in sharding.device_set)
        and len(shape) == 2 and shape[0] == shape[1]
        and jnp.dtype(dtype) == jnp.float32
        and held_bytes(shape[0]) <= VMEM_BUDGET
    )


def hold(G: jax.Array) -> jax.Array:
    """G (m, m) as the kernel holds it: (m, R, 128), row j's entries in lanes,
    the padded lanes zeros."""
    m = G.shape[0]
    r = _rows(m)
    return jnp.pad(G, ((0, 0), (0, r * _LANE - m))).reshape(m, r, _LANE)


def _kernel(scalars_ref, g_ref, c_ref, theta_ref, out_ref, *, m: int):
    lam, n = scalars_ref[0], scalars_ref[1]
    shape = c_ref.shape
    index = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANE + jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def pick(hit, v):  # the one entry of v where hit is, as a (1, 1) value
        return jnp.sum(jnp.sum(jnp.where(hit, v, 0.0), axis=1, keepdims=True), axis=0, keepdims=True)

    def step(j, carry, intercept=False):
        c, th = carry
        g = g_ref[j]  # row j of G, (R, 128)
        hit = index == j
        th_j = pick(hit, th)
        rho = (pick(hit, c) + th_j * pick(hit, g)) / n
        new = rho if intercept else jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0)
        return c - (new - th_j) * g, jnp.where(hit, new, th)

    carry = step(0, (c_ref[...], theta_ref[...]), intercept=True)
    out_ref[...] = jax.lax.fori_loop(1, m, step, carry)[1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sweep(held: jax.Array, c: jax.Array, theta: jax.Array, lam, n, interpret: bool = False) -> jax.Array:
    """θ after one sweep of coordinate steps 0 .. m - 1, as one kernel named
    ``lasso_cd_sweep``: ``held`` is G as :func:`hold` gives it, ``c`` (m,) is
    ``cy - G θ`` at the sweep's head, ``theta`` (m,) the iterate before it,
    ``lam`` and ``n`` scalars. Returns θ (m,)."""
    m, r, _ = held.shape

    def vector(v):  # (m,) -> (R, 128), entry i at sublane i // 128, lane i % 128
        return jnp.pad(v.astype(jnp.float32), (0, r * _LANE - m)).reshape(r, _LANE)

    scalars = jnp.stack([jnp.asarray(lam, jnp.float32), jnp.asarray(n).astype(jnp.float32)])
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, m=m),
        out_shape=jax.ShapeDtypeStruct((r, _LANE), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem],
        out_specs=vmem,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET + (4 << 20)),
        interpret=interpret,
        name="lasso_cd_sweep",
    )(scalars, held, vector(c), vector(theta))
    return out.reshape(-1)[:m]
